"""Threshold-mask block compaction into fixed-width staging rows.

The TPU-native replacement for the GPU's variable-length masked write
(paper §3.3): each block of the flat gradient selects its |x| > thres
elements and compacts them, in index order, into a fixed per-block
staging buffer of width ``bcap``.  Global indices are reconstructed in
ops.py as i*B + offset.  The kernel is the fused pipeline's
``ef_fused.compact_residual`` without the residual write.

Outputs (per block row i):
  vals   (nblocks, bcap) f32   selected values, in index order
  offs   (nblocks, bcap) i32   local offsets (SENTINEL = -1 padding)
  counts (nblocks,)      i32   #selected in block i (uncapped)
"""
from __future__ import annotations

import functools

import jax

from repro.core.codec import SENTINEL  # noqa: F401  (re-exported)


@functools.partial(jax.jit, static_argnames=("bcap", "block", "interpret"))
def threshold_compact(x2d: jax.Array, thres: jax.Array, *, bcap: int,
                      block: int = 2048, interpret: bool = True):
    from repro.kernels.ef_fused.compact_residual import compact_residual

    vals, offs, cnts, _ = compact_residual(
        x2d, None, thres, bcap=bcap, k_cap=x2d.shape[0] * bcap, block=block,
        with_resid=False, interpret=interpret,
        backend="interpret" if interpret else "mosaic")
    return vals, offs, cnts
