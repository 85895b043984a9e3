"""Single-pass moments (sum, sum-of-squares, abs-max) of a blocked vector.

Algorithm 1 line 2 of the paper computes mean/std of the d-dimensional
accumulated gradient every iteration.  On TPU all three statistics fuse
into ONE pass over HBM.  The kernel is pass A of the fused EF pipeline
(``ef_fused.fused_moments``) run on a single operand, so the unfused
pipeline's statistics are bit-for-bit the fused pipeline's.
"""
from __future__ import annotations

import functools

import jax


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def moments(x2d: jax.Array, *, block: int = 2048, interpret: bool = True):
    """Return (sum, sumsq, absmax) of a (nblocks, block) f32/bf16 array."""
    from repro.kernels.ef_fused.fused_moments import fused_moments

    s, sq, mx, _ = fused_moments(
        x2d, None, block=block, interpret=interpret,
        backend="interpret" if interpret else "mosaic")
    return s, sq, mx
