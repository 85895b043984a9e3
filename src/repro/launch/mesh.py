"""Production meshes.  Functions, not module constants — importing this
module never touches jax device state.

Every mesh is built with ``AxisType.Auto`` axes.  ``jax.make_mesh``
defaults to Explicit axes, under which sharding becomes part of each
array's type; the train step is written for GSPMD propagation (manual
``shard_map`` over the data axes, auto over ``model``), so it asks for
Auto axes instead of carrying explicit shardings through every op.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """A mesh of ``shape`` over ``axes`` with Auto axis types (e.g. (4, 2)
    on 8 CPU devices with xla_force_host_platform_device_count=8)."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes, (AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips single pod; 2x16x16 = 512 chips across 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes_of(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis_size(mesh) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))["model"]


def data_world_size(mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    w = 1
    for a in data_axes_of(mesh):
        w *= sizes[a]
    return w
