"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (device counts are locked at first jax initialization, and
tests/benches must see the real single CPU device, not the dry-run's 512
placeholders).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes: the sharding rules place arrays with
    ``with_sharding_constraint`` and let the partitioner propagate, which
    Explicit axes (``jax.make_mesh``'s default) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips as (data=16, model=16).
    Multi-pod: 2 pods x 256 chips as (pod=2, data=16, model=16)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Whatever this host actually has (CPU tests: 1 device -> (1,1))."""
    n = jax.device_count()
    dp = n // model_parallel
    return make_mesh((dp, model_parallel), ("data", "model"))
