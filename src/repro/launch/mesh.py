"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — jax locks the device count on
first backend initialization.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh", "mesh_axis_sizes"]


def make_production_mesh(*, multi_pod: bool = False):
    """The assignment's production mesh: 16x16 = 256 chips per pod
    ("data", "model"), or 2 pods = 512 chips ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (small-mesh tests, examples). Every axis is Auto:
    ``jax.make_mesh`` defaults to Explicit axes, which the shard_map and
    GSPMD code here does not accept."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def mesh_axis_sizes(mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
