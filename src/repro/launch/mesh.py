"""Production mesh builders.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the "pod" axis composes
with "data" for cross-pod data parallelism (gradient all-reduce crosses DCN).

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (smoke tests see 1 device; only dryrun.py forces 512).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto: the sharding rules place
    arrays through ``with_sharding_constraint``, not explicit axes. Takes
    the first ``prod(shape)`` devices."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    return make_mesh((data, model), ("data", "model"))
