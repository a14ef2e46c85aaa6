"""Production mesh factory.

A function, not a module-level constant, so importing this module never
touches jax device state (device count is locked on first jax init).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis explicitly Auto-sharded."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """(data, model) mesh over this host's devices; asking for more devices
    than exist is an error, never a smaller mesh."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"mesh data={data} x model={model} needs "
                         f"{data * model} devices; {n} exist")
    return make_mesh((data, model), ("data", "model"))
