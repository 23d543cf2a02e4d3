"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches JAX device state — the dry-run must set XLA_FLAGS before any
device query happens.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes) -> Mesh:
    """jax.make_mesh with every axis ``AxisType.Auto``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2x16x16 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 4) -> Mesh:
    """Small mesh for CPU multi-device tests."""
    return make_mesh((data, model), ("data", "model"))
