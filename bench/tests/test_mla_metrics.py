"""The latent-attention readers (`mla_decode_ms_per_step`,
`mla_decode_roofline`, `mla_chunk_ms_per_chunk`) and the bytes behind the
roofline: a hand count at the 13-layer widths, numbers on a trace built by
hand with the program's `_mla` modules, and nothing on the small trace
recorded from a program without them."""
import json
import os

import pytest

from bench.lib import program_trace as pt
from bench.models import deepseek_v2_bytes as mb
from bench.tests.test_metrics import make_ctx, reader
from bench.tests.test_program_trace import OPS, STEP, _pd
from bench.tests.test_trace import SMALL

CONFIGS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "configs")
READERS = ("mla_decode_ms_per_step", "mla_decode_roofline", "mla_chunk_ms_per_chunk")
PEAKS = {"bf16_flop_s": 197e12, "hbm_byte_s": 819e9}


def cfg(name="deepseek-v2-lite-13l-4k"):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_deepseek_v2_lite_13l_decode_bytes_hand_count():
    c = cfg()
    attn = (2048 * 16 * 192                 # wq: nope 128 + rope 64 a head
            + 2048 * 576                    # wkv_a: latent 512 + rope key 64
            + 512 * 16 * 256                # wkv_b: latent to k_nope and v
            + 16 * 128 * 2048)              # wo
    assert 2 * attn == 27_525_120           # 27.5 MB a layer in bf16
    norms = 2 * (2048 + 512 + 2048)         # attention, latent, feed-forward
    router = 4 * 2048 * 64                  # f32
    layer = 2 * attn + norms + router
    assert mb.layer_weight_bytes(c) == layer == 28_058_624
    assert mb.position_bytes(c) == 2 * 576
    assert mb.n_moe_layers(c) == 12         # the dense first layer is not
    want = 12 * (10 * layer + 1152 * (100 + 200))
    assert want == 3_371_182_080
    assert mb.decode_bytes(c, 10, [100, 200]) == want


# the program's module names for a deepseek_v2 layer stack: the MoE layers'
# decode and chunk attention, and the dense first layer's own modules
MLA_MODULES = [("jit_pre_decode_mla_batched(11)", 20, 30),
               ("jit_pre_decode_mla_batched(11)", 60, 65),
               ("jit_dense_decode_mla(12)", 10, 20),
               ("jit_pre_prefill_chunk_mla_kv64(13)", 120, 128),
               ("jit_pre_prefill_chunk_mla_kv32(14)", 130, 134),
               ("jit_dense_prefill_chunk_mla_kv64(15)", 110, 120)]


@pytest.fixture
def readers(monkeypatch):
    view = {}
    monkeypatch.setattr(pt, "load", lambda ctx, where: view.get("r"))
    return view, {n: reader(n) for n in READERS}


def test_readers_by_hand(readers):
    view, rd = readers
    view["r"] = pt.reduce(_pd(OPS, STEP, MLA_MODULES))
    ctx = make_ctx([], {}, cfg=cfg(), peaks=PEAKS, ctx_len=[100, 200],
                   trace={"busy_s": 0, "window_s": 0})
    # one decode step: 15 ns of MLA decode (the dense layer's 10 ns apart);
    # one prefill chunk: 12 ns over two buckets
    assert rd["mla_decode_ms_per_step"].read(ctx) == pytest.approx(15e-6)
    assert rd["mla_chunk_ms_per_chunk"].read(ctx) == pytest.approx(12e-6)
    need = 12 * (28_058_624 + 1152 * 300)
    assert rd["mla_decode_roofline"].read(ctx) == pytest.approx(
        100 * need / 15e-9 / 819e9)


def test_roofline_needs_a_peak_and_a_latent(readers):
    view, rd = readers
    view["r"] = pt.reduce(_pd(OPS, STEP, MLA_MODULES))
    assert rd["mla_decode_roofline"].read(make_ctx(
        [], {}, cfg=cfg(), trace={})) is None
    assert rd["mla_decode_roofline"].read(make_ctx(
        [], {}, cfg=cfg("olmoe-1b-7b"), peaks=PEAKS, trace={})) is None


def test_readers_read_nothing_without_mla_modules(readers):
    view, rd = readers
    ctx = make_ctx([], {}, cfg=cfg(), peaks=PEAKS, ctx_len=[100],
                   trace={"busy_s": 0, "window_s": 0})
    from jax.profiler import ProfileData
    view["r"] = pt.reduce(ProfileData.from_file(SMALL))
    for m in rd.values():
        assert m.read(ctx) is None
    # olmoe's own modules: spans and modules, but none of MLA's
    gqa = [("jit_pre_decode_batched(1)", 20, 30),
           ("jit_pre_prefill_chunk_kv64(2)", 120, 128)]
    view["r"] = pt.reduce(_pd(OPS, STEP, gqa))
    for m in rd.values():
        assert m.read(ctx) is None
    view["r"] = None
    for m in rd.values():
        assert m.read(ctx) is None
