"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device state
(the dry-run must set XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """jax.make_mesh with every axis Auto (sharding propagated by GSPMD)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; multi-pod prepends a 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """Single-device mesh with the same axis names (CPU tests/examples)."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))
