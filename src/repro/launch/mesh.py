"""Production mesh definitions.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.
"""
from __future__ import annotations

import jax



def make_mesh(shape: tuple, axes: tuple):
    """jax.make_mesh with Auto axis types (sharding propagated by XLA)."""
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 v5e pod (data, model); 2 pods add a leading 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def dp_axes(mesh) -> tuple[str, ...]:
    """Data-parallel axes: ('pod','data') multi-pod, ('data',) single-pod."""
    names = mesh.axis_names
    return tuple(a for a in names if a != "model")


def make_host_mesh(n: int | None = None, name: str = "workers"):
    """Flat mesh over available devices (tests, examples, graph engine)."""
    n = n or len(jax.devices())
    return make_mesh((n,), (name,))
