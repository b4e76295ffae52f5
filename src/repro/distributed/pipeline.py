"""Pipeline parallelism over the pod axis (GPipe-style, shard_map +
collective_permute).

The default multi-pod configuration runs the pod axis as pure data parallel,
but for models whose layer stack exceeds one pod's memory the launcher can
flip the pod axis to pipeline stages: each pod holds `num_units /
n_stages` of the layer scan, microbatches stream through with
`jax.lax.ppermute`, and the bubble fraction is (S-1)/(M+S-1).

This module provides the stage-loop building block used by
`launch/train.py --pipeline`; it is also lowered stand-alone in tests to
prove the collective-permute schedule is coherent.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_stages(stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                    n_stages: int, n_microbatches: int,
                    axis_name: str = "pod"):
    """Returns pipelined(x_microbatches, stage_params) for use in shard_map.

    stage_fn(params, x) is ONE stage's compute. Inside shard_map each device
    group holds its stage's params; microbatches rotate via ppermute.
    x_microbatches: (M, mb, ...) stacked microbatches (stage 0's input).
    """
    S, M = n_stages, n_microbatches
    assert M >= 1

    def pipelined(stage_params, x_mb):
        stage = jax.lax.axis_index(axis_name)
        T = M + S - 1     # total ticks
        mb_shape = x_mb.shape[1:]

        def tick(carry, t):
            buf, outputs = carry
            # stage 0 injects microbatch t (if in range); others use buf
            inject = jnp.where(t < M, t, M - 1)
            x_in = jnp.where(stage == 0,
                             x_mb[inject],
                             buf)
            y = stage_fn(stage_params, x_in)
            # pass activations to the next stage
            fwd = [(i, i + 1) for i in range(S - 1)] + [(S - 1, 0)]
            buf_next = jax.lax.ppermute(y, axis_name, perm=fwd)
            # the last stage's output at tick t corresponds to microbatch
            # t - (S - 1); collect it
            mb_idx = t - (S - 1)
            take = (stage == S - 1) & (mb_idx >= 0)
            outputs = jnp.where(
                take,
                outputs.at[jnp.maximum(mb_idx, 0)].set(y),
                outputs)
            return (buf_next, outputs), None

        buf0 = jnp.zeros(mb_shape, x_mb.dtype)
        outs0 = jnp.zeros((M,) + mb_shape, x_mb.dtype)
        (_, outputs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(T))
        return outputs

    return pipelined


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)


def make_pipelined_forward(mesh: Mesh, stage_fn, n_stages: int,
                           n_microbatches: int):
    """shard_map wrapper: params sharded by stage on the pod axis."""
    pipelined = pipeline_stages(stage_fn, n_stages, n_microbatches, "pod")

    def fwd(stage_params, x_mb):
        return pipelined(stage_params, x_mb)

    return jax.shard_map(
        fwd, mesh=mesh,
        in_specs=(P("pod"), P(None, "data")),
        out_specs=P(None, "data"),
        check_vma=False)
