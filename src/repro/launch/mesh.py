"""Production mesh construction.

Every mesh axis is `AxisType.Auto`: the sharding annotations are hints
that GSPMD propagates (`jax.make_mesh` now defaults to explicit axes).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before any device
initialization).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_parallel: int = 1):
    """Whatever devices exist locally (CPU tests / examples)."""
    n = len(jax.devices())
    mp = min(model_parallel, n)
    return jax.make_mesh((n // mp, mp), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
