"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — device count is locked
on first jax init, and only dryrun.py forces the 512-device host platform.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.config import MULTI_POD_MESH, SINGLE_POD_MESH, MeshConfig


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the models place activations with with_sharding_constraint,
    # which refers only to Auto axes (jax.make_mesh defaults to Explicit)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def production_mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    return MULTI_POD_MESH if multi_pod else SINGLE_POD_MESH
