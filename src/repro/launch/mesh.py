"""Production mesh construction (assignment spec).

A FUNCTION, not a module-level constant — importing this module must not
touch jax device state (the dry-run sets XLA_FLAGS before any jax init).
"""
from __future__ import annotations

import jax

__all__ = ["make_mesh_auto", "make_production_mesh", "make_local_mesh"]


def make_mesh_auto(shape, axes):
    """``jax.make_mesh`` with every axis of the Auto type (sharding left
    to the compiler, as :func:`jax.jit` in/out shardings expect)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_auto(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU smoke)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, n // data)
    return make_mesh_auto((data, model), ("data", "model"))
