"""Pallas TPU kernel: grouped expert FFN (the MoE compute hot-spot).

Fuses gate/up projections, SiLU, and down projection for one (expert,
token-tile, ff-tile) grid cell; the down-projection reduces over ff tiles by
accumulating into the output block (revisited consecutively because the ff
axis is the innermost grid dimension). All matmul tiles are MXU-aligned
(multiples of 128 where shapes allow) and sized to keep the working set
(x + wg + wu + wd + out ≈ 5 blocks) within VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def silu(x):
    """`jax.nn.silu` spelled out in x's dtype: Mosaic cannot lower a bf16
    logistic, and this is the op sequence XLA expands that logistic to, so
    the kernels round where XLA's own silu does."""
    return x * (1 / (1 + jnp.exp(-x)))


def ffn_block(x, wg, wu, wd):
    """One d_ff tile of an expert FFN: (Cb, D) rows -> (Cb, D) f32 partial
    of the down projection. The numerics contract of every expert FFN
    kernel and its XLA reference: the gate and up projections accumulate
    in f32 and round to the model dtype before silu(g) * u (taken in the
    model dtype); the down projection accumulates in f32, across d_ff
    tiles too."""
    f32 = jnp.float32
    g = jnp.dot(x, wg, preferred_element_type=f32).astype(x.dtype)
    u = jnp.dot(x, wu, preferred_element_type=f32).astype(x.dtype)
    return jnp.dot(silu(g) * u, wd, preferred_element_type=f32)


def _ffn_kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref):
    ft = pl.program_id(2)
    part = ffn_block(x_ref[0], wg_ref[0], wu_ref[0], wd_ref[0])

    @pl.when(ft == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[0] += part


@functools.partial(jax.jit,
                   static_argnames=("block_c", "block_f", "interpret"))
def expert_ffn(x: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
               w_down: jnp.ndarray, *, block_c: int = 128,
               block_f: int = 128, interpret: bool = False) -> jnp.ndarray:
    """x: (E, C, D); w_gate/w_up: (E, D, F); w_down: (E, F, D) -> (E, C, D) f32.

    C must divide by block_c and F by block_f (callers pad the dispatch
    buffer, which is already capacity-padded).
    """
    E, C, D = x.shape
    F = w_gate.shape[-1]
    block_c = min(block_c, C)
    block_f = min(block_f, F)
    assert C % block_c == 0 and F % block_f == 0, (C, block_c, F, block_f)
    grid = (E, C // block_c, F // block_f)
    return pl.pallas_call(
        _ffn_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, D), lambda e, c, f: (e, c, 0)),
            pl.BlockSpec((1, D, block_f), lambda e, c, f: (e, 0, f)),
            pl.BlockSpec((1, D, block_f), lambda e, c, f: (e, 0, f)),
            pl.BlockSpec((1, block_f, D), lambda e, c, f: (e, f, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_c, D), lambda e, c, f: (e, c, 0)),
        out_shape=jax.ShapeDtypeStruct((E, C, D), jnp.float32),
        interpret=interpret,
    )(x, w_gate, w_up, w_down)
