"""Jitted public wrappers for the Pallas kernels.

On a TPU the kernels compile to Mosaic. On the CPU they run in interpret
mode (the kernel body runs as traced Python/jnp — numerics validated
against `ref.py`), which is how the tests exercise them. Any other backend
is an error: there is no silent fallback to the interpreter.
"""
from __future__ import annotations

import jax

from repro.kernels import moe_gemm, slot_gather, topk_gating
from repro.kernels import ref as ref_ops


def _default_interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"Pallas kernels compile for TPU only (and run "
                           f"interpreted on CPU), not on {backend!r}")
    return backend == "cpu"


def expert_ffn(x, w_gate, w_up, w_down, *, block_c: int = 128,
               block_f: int = 128, interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return moe_gemm.expert_ffn(x, w_gate, w_up, w_down, block_c=block_c,
                               block_f=block_f, interpret=interpret)


def topk(logits, k: int, *, norm: bool = True, block_t: int = 256,
         interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return topk_gating.topk_gating(logits, k, norm=norm, block_t=block_t,
                                   interpret=interpret)


def slot_ffn(x, slot_of_group, s_gate, s_up, s_down, *, block_c: int = 128,
             block_f: int = 1024, interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return slot_gather.slot_ffn(x, slot_of_group, s_gate, s_up, s_down,
                                block_c=block_c, block_f=block_f,
                                interpret=interpret)


# re-export oracles for tests/benchmarks
expert_ffn_ref = ref_ops.expert_ffn_ref
topk_ref = ref_ops.topk_gating_ref
slot_ffn_ref = ref_ops.slot_ffn_ref
