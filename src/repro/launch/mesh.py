"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any device query).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the models place activations with with_sharding_constraint
    # and leave the rest to the partitioner; jax.make_mesh's default
    # Explicit axes reject that
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e pod slice: 16x16 = 256 chips per pod; 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Tiny mesh over whatever devices exist (CPU tests)."""
    n = len(jax.devices())
    data = n // model_axis
    return _auto_mesh((data, model_axis), ("data", "model"))
