"""Production mesh builders.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any
jax initialisation, and smoke tests must keep seeing 1 device.

Mesh shapes mirror the paper's hierarchy limit: physical XBARs top out at
16x16, so scale-up goes hierarchical — our axes are capped at 16 and the
pod axis adds the second hierarchy level (2 pods x 256 chips).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(data: int = 2, model: int = 2, *, pod: int | None = None):
    """Small mesh for CPU tests (requires >= data*model fake devices)."""
    if pod:
        return jax.make_mesh(
            (pod, data, model), ("pod", "data", "model"),
            axis_types=(AxisType.Auto,) * 3,
        )
    return jax.make_mesh((data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2)


def make_serve_mesh(num_shards: int = 4, *, axis: str = "data"):
    """1-D mesh the sharded serving engine partitions its page pool
    over: ``num_shards`` devices along one named axis.  Requires at
    least ``num_shards`` (fake or real) devices — CI forces them with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``."""
    return jax.make_mesh((num_shards,), (axis,), axis_types=(AxisType.Auto,))
