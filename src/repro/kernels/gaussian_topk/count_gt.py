"""Count elements with |x| > threshold (one HBM pass).

This is the inner reduction of Algorithm 1's refinement loop (lines 6-7):
each refinement iteration re-counts the mask at the adjusted threshold.
The kernel is the fused pipeline's ``ef_fused.tree_count`` at a single
threshold.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def count_gt(x2d: jax.Array, thres: jax.Array, *, block: int = 2048,
             interpret: bool = True) -> jax.Array:
    """# of elements of (nblocks, block) ``x2d`` with |x| > thres (scalar)."""
    from repro.kernels.ef_fused.tree_count import tree_count

    t = jnp.asarray(thres, jnp.float32).reshape(1)
    return tree_count(x2d, None, t, n_t=1, block=block, interpret=interpret,
                      backend="interpret" if interpret else "mosaic")[0]
