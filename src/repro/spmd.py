"""Running a Mosaic kernel inside the train step's partly manual region.

GSPMD cannot partition a Mosaic kernel, and lowering refuses one in a
region that is manual over some mesh axes only: the train step's
``shard_map`` is manual over the data axes and automatic over ``model``.
So a kernel's call (the EF row blocks, the expert layer's grouped
products) runs in a nested ``shard_map`` that makes the remaining axes
manual too.  This module sits below ``models`` and ``kernels``; both
call it.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P


def auto_axes() -> dict:
    """The current mesh's axes that are still automatic, with their
    sizes; empty outside a mesh or in a fully manual region."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return {}
    return {a: n for a, n, t in zip(mesh.axis_names, mesh.axis_sizes,
                                     mesh.axis_types) if t != AxisType.Manual}


def manual_over_auto_axes(fn, *args, in_specs=P(), out_specs=P()):
    """Call ``fn`` with every mesh axis that is still auto made manual.

    By default the operands and results are replicated over those axes:
    every device computes every row, the values GSPMD would have
    replicated.  A caller whose operands are split over an axis passes
    the split as ``in_specs`` / ``out_specs`` (one spec per argument,
    as ``jax.shard_map`` takes them) and reduces inside ``fn``.  Outside
    a mesh, or in a region that is already fully manual, ``fn`` is
    called as is.
    """
    auto = auto_axes()
    if not auto:
        return fn(*args)
    return jax.shard_map(fn, mesh=jax.sharding.get_abstract_mesh(),
                         in_specs=in_specs, out_specs=out_specs,
                         axis_names=set(auto), check_vma=False)(*args)
