"""The main path's Pallas kernels, compiled for a described TPU v5e chip at
the widths they serve: olmoe-1b-7b (d_model 2048, 64 experts of d_ff 1024,
top-8, 16 MHA heads of 128) and, for MLA decode attention,
deepseek-v2-lite (kv_lora_rank 512, rope dim 64). Nothing runs: the TPU
compiler refuses here what the chip would refuse (block shapes off the
tiling, scoped-VMEM overruns, primitives Mosaic cannot lower).

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
H, HD = 16, 128                          # olmoe-1b-7b attention
B, MAX_SEQ = 4, 1024                     # serving batch and KV length
SLOTS = 38 * 16                          # capacity 0.6 x 64, 16 layers
MLA_H, MLA_R, MLA_P = 16, 512, 64        # deepseek-v2-lite MLA


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


@pytest.mark.parametrize("cap", [B * K, 32 * K], ids=["decode", "chunk"])
def test_slot_ffn_compiles(one_chip, cap):
    _compile(one_chip, functools.partial(ops.slot_ffn, interpret=False),
             ((E, cap, D), bf16), ((E,), i32), ((SLOTS, D, F), bf16),
             ((SLOTS, D, F), bf16), ((SLOTS, F, D), bf16))


def test_expert_ffn_compiles(one_chip):
    _compile(one_chip, functools.partial(ops.expert_ffn, interpret=False),
             ((E, 128, D), bf16), ((E, D, F), bf16), ((E, D, F), bf16),
             ((E, F, D), bf16))


def test_topk_gating_compiles(one_chip):
    _compile(one_chip, functools.partial(ops.topk, k=K, norm=False,
                                         interpret=False),
             ((256, E), f32))


def test_fused_moe_entry_compiles(one_chip):
    _compile(one_chip, functools.partial(ops.fused_moe_entry, top_k=K,
                                         norm_topk=False, interpret=False),
             ((B, D), bf16), ((D, E), f32), ((E,), f32), ((E,), i32),
             ((SLOTS, D, F), bf16), ((SLOTS, D, F), bf16),
             ((SLOTS, F, D), bf16))


def test_fused_decode_attention_compiles(one_chip):
    _compile(one_chip,
             functools.partial(ops.fused_decode_attention, interpret=False),
             ((B, 1, H, HD), bf16), ((B, 1, H, HD), bf16),
             ((B, 1, H, HD), bf16), ((B, MAX_SEQ, H, HD), bf16),
             ((B, MAX_SEQ, H, HD), bf16), ((B,), i32))


def test_fused_mla_decode_attention_compiles(one_chip):
    _compile(one_chip,
             functools.partial(ops.fused_mla_decode_attention,
                               scale=(128 + MLA_P) ** -0.5, interpret=False),
             ((B, MLA_H, MLA_R), f32), ((B, MLA_H, MLA_P), f32),
             ((B, MLA_R), bf16), ((B, MLA_P), bf16),
             ((B, MAX_SEQ, MLA_R), bf16), ((B, MAX_SEQ, MLA_P), bf16),
             ((B,), i32))
