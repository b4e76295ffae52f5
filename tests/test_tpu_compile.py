"""The main path's Pallas kernels, compiled for a described TPU v5e chip at
the widths they serve: olmoe-1b-7b (d_model 2048, 64 experts of d_ff 1024,
top-8) and, for the expert FFN's tiling, deepseek-v2-lite (d_ff 1408).
Nothing runs: the TPU compiler refuses here what the chip would refuse
(block shapes off the tiling, scoped-VMEM overruns, primitives Mosaic
cannot lower).

The topology is described inside a fixture, never at import, so every
pytest worker collects the same tests and only the one running this file
loads the TPU compiler."""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

D, E, F, K = 2048, 64, 1024, 8           # olmoe-1b-7b expert block
SLOTS = 38 * 16                          # capacity 0.6 x 64, 16 layers
DSV2_F = 1408                            # deepseek-v2-lite expert d_ff


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.mark.parametrize("cap,f", [(8, F), (32, F), (8, DSV2_F),
                                   (32, DSV2_F)],
                         ids=["decode", "chunk", "decode_dsv2", "chunk_dsv2"])
def test_slot_ffn_compiles(one_chip, cap, f):
    """One group of `cap` rows per expert (a batch of 8 decoding, a
    32-token prefill chunk); deepseek-v2-lite's d_ff of 1408 tiles by 128."""
    _compile(one_chip, functools.partial(ops.slot_ffn, interpret=False),
             ((E, cap, D), bf16), ((E,), i32), ((SLOTS, D, f), bf16),
             ((SLOTS, D, f), bf16), ((SLOTS, f, D), bf16))


def test_expert_ffn_compiles(one_chip):
    _compile(one_chip, functools.partial(ops.expert_ffn, interpret=False),
             ((E, 128, D), bf16), ((E, D, F), bf16), ((E, D, F), bf16),
             ((E, F, D), bf16))


def test_topk_gating_compiles(one_chip):
    _compile(one_chip, functools.partial(ops.topk, k=K, norm=False,
                                         interpret=False),
             ((256, E), f32))
