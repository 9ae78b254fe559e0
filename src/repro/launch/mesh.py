"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state.  Single-pod: (16, 16) = 256 chips ("data", "model");
multi-pod: (2, 16, 16) = 512 chips ("pod", "data", "model").
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the shardings are given explicitly at jit boundaries
    # (jax.make_mesh defaults to Explicit axes, sharding-in-types)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def data_axes(mesh) -> tuple[str, ...] | str:
    """The batch-sharding axes: ('pod','data') on multi-pod, 'data' otherwise."""
    return ("pod", "data") if "pod" in mesh.axis_names else "data"


def axis_size(mesh, names) -> int:
    if isinstance(names, str):
        names = (names,)
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n
