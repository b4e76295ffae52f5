"""Pallas TPU kernel: slot-indirect expert FFN (ExpertFlow's cache read path).

The expert weights live in a bounded slot buffer (S < E slots); the
(layer, expert) -> slot table is a scalar-prefetch operand, and the BlockSpec
index maps perform the indirection — weight tiles stream HBM->VMEM directly
from the right slot with NO materialized gather copy. This is the TPU-native
replacement for the paper's GPU pointer-chase into the expert cache.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _slot_ffn_kernel(slot_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref):
    ft = pl.program_id(2)
    x = x_ref[0]
    g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    part = jnp.dot(h, wd_ref[0], preferred_element_type=jnp.float32)

    @pl.when(ft == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[0] += part


def _fit_block(n: int, want: int) -> int:
    """Largest divisor of n that is <= want (tile sizes must divide the
    axis; callers on real TPUs should pass aligned shapes, interpret mode
    accepts anything)."""
    b = min(want, n)
    while n % b:
        b -= 1
    return b


@functools.partial(jax.jit, static_argnames=("block_c", "block_f",
                                             "interpret"))
def slot_ffn(x: jnp.ndarray, slot_of_expert: jnp.ndarray,
             s_gate: jnp.ndarray, s_up: jnp.ndarray, s_down: jnp.ndarray, *,
             block_c: int = 128, block_f: int = 128,
             interpret: bool = False) -> jnp.ndarray:
    """x: (E, C, D) dispatch buffer; slot_of_expert: (E,) int32 (valid);
    slot buffers (S, D, F) / (S, F, D). Returns (E, C, D) float32."""
    E, C, D = x.shape
    F = s_gate.shape[-1]
    block_c = _fit_block(C, block_c)
    block_f = _fit_block(F, block_f)
    grid = (E, C // block_c, F // block_f)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, D), lambda e, c, f, s: (e, c, 0)),
            pl.BlockSpec((1, D, block_f), lambda e, c, f, s: (s[e], 0, f)),
            pl.BlockSpec((1, D, block_f), lambda e, c, f, s: (s[e], 0, f)),
            pl.BlockSpec((1, block_f, D), lambda e, c, f, s: (s[e], f, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_c, D), lambda e, c, f, s: (e, c, 0)),
    )
    return pl.pallas_call(
        _slot_ffn_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((E, C, D), jnp.float32),
        interpret=interpret,
    )(slot_of_expert.astype(jnp.int32), x, s_gate, s_up, s_down)
