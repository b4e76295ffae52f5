"""The deepseek-v2-lite configuration as run: its `rope_scaling` group holds
the published YaRN numbers with `factor` 1, which is plain RoPE. Checked
with the published YaRN formulas (DeepSeek-V2's modeling code), so the
reference and the program, which implement no YaRN, compute the model the
file names."""
import json
import math
import os

import numpy as np
import pytest

CONFIGS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "configs")


def cfg(name="deepseek-v2-lite-13l-4k"):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn(c):
    """(inverse frequencies, cos/sin multiplier, softmax-scale multiplier)."""
    rs, dim, base = c["rope_scaling"], c["qk_rope_head_dim"], c["rope_theta"]
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inv_freq = extra / factor * ramp + extra * (1 - ramp)
    return (inv_freq,
            yarn_mscale(factor, rs["mscale"]) / yarn_mscale(factor, rs["mscale_all_dim"]),
            yarn_mscale(factor, rs["mscale_all_dim"]) ** 2)


def test_rope_scaling_as_run_is_plain_rope():
    c = cfg()
    assert c["rope_scaling"]["type"] == "yarn" and c["rope_scaling"]["factor"] == 1
    half = c["qk_rope_head_dim"] // 2
    plain = c["rope_theta"] ** (-np.arange(half) / half)     # bench/models/common.rope
    inv_freq, cos_scale, softmax_scale = yarn(c)
    np.testing.assert_allclose(inv_freq, plain, rtol=1e-15)
    assert cos_scale == softmax_scale == 1.0
    assert c["max_position_embeddings"] == c["rope_scaling"]["original_max_position_embeddings"]


def test_published_yarn_would_change_the_function():
    c = cfg()
    c["rope_scaling"] = dict(c["rope_scaling"], factor=c["published"]["rope_scaling"]["factor"])
    half = c["qk_rope_head_dim"] // 2
    inv_freq, cos_scale, softmax_scale = yarn(c)
    assert cos_scale == 1.0                                  # mscale == mscale_all_dim
    assert softmax_scale == pytest.approx(1.590, abs=1e-3)   # (0.1 * 0.707 * ln 40 + 1)^2
    assert not np.allclose(inv_freq, c["rope_theta"] ** (-np.arange(half) / half))
