"""Jitted public wrappers for the Pallas kernels.

On a TPU the kernels compile to Mosaic. On the CPU they run in interpret
mode (the kernel body runs as traced Python/jnp — numerics validated
against `ref.py`), which is how the tests exercise them. Any other backend
is an error: there is no silent fallback to the interpreter.
"""
from __future__ import annotations

import jax

from repro.kernels import (decode_superkernel, moe_gemm, slot_gather,
                           topk_gating)
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


def slot_ffn(x, slot_of_expert, s_gate, s_up, s_down, *, block_c: int = 128,
             block_f: int = 128, interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return slot_gather.slot_ffn(x, slot_of_expert, s_gate, s_up, s_down,
                                block_c=block_c, block_f=block_f,
                                interpret=interpret)


def fused_moe_entry(x, router_w, logit_bias, slot_of_expert, s_gate, s_up,
                    s_down, *, top_k: int, norm_topk: bool = True,
                    interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return decode_superkernel.fused_moe_entry(
        x, router_w, logit_bias, slot_of_expert, s_gate, s_up, s_down,
        top_k=top_k, norm_topk=norm_topk, interpret=interpret)


def fused_decode_attention(q, k_new, v_new, k_cache, v_cache, cache_len, *,
                           logit_softcap: float = 0.0, scale=None,
                           block_s: int = 128, interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return decode_superkernel.fused_decode_attention(
        q, k_new, v_new, k_cache, v_cache, cache_len,
        logit_softcap=logit_softcap, scale=scale, block_s=block_s,
        interpret=interpret)


def fused_mla_decode_attention(q_abs, q_pe, c_new, pe_new, latent, pe,
                               cache_len, *, scale: float, block_s: int = 128,
                               interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return decode_superkernel.fused_mla_decode_attention(
        q_abs, q_pe, c_new, pe_new, latent, pe, cache_len, scale=scale,
        block_s=block_s, interpret=interpret)


# re-export oracles for tests/benchmarks
expert_ffn_ref = ref_ops.expert_ffn_ref
topk_ref = ref_ops.topk_gating_ref
slot_ffn_ref = ref_ops.slot_ffn_ref
fused_moe_entry_ref = ref_ops.fused_moe_entry_ref
