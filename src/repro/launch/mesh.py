"""Production meshes.

Single pod: 256 chips as (data=16, model=16) — TP stays inside the pod's
ICI where the 16-way axis has full bisection bandwidth.  Multi-pod: the
``pod`` axis (DCN-connected) composes with ``data`` for batch parallelism
only, so the sole cross-pod collective in a train step is the gradient
reduction (see parallel/sharding.py).

Functions, not module constants: importing this module must never touch
jax device state (the dry-run pins device count via XLA_FLAGS before any
jax initialization).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Local mesh over whatever devices exist (smoke tests, examples)."""
    n = jax.device_count()
    assert n % model == 0, (n, model)
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
