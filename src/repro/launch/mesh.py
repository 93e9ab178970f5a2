"""Mesh construction (single-pod 16x16, multi-pod 2x16x16, host meshes).

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the model code shards through
    GSPMD propagation and ``with_sharding_constraint``, which Explicit axes
    (``jax.make_mesh``'s default) reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """(data, model) mesh over every device this process sees, with
    ``model_parallel`` devices on the model axis."""
    n = len(jax.devices())
    if model_parallel < 1 or n % model_parallel:
        raise ValueError(f"model_parallel={model_parallel} does not divide "
                         f"the {n} available device(s)")
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))
