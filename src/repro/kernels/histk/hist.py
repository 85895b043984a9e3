"""One-pass log2-magnitude histogram of |x|.

Beyond-paper optimization (DESIGN.md §Perf): Algorithm 1 needs up to four
extra count passes over u to refine the ppf threshold.  A 64-bin histogram
over exponent buckets of |x| is computed in ONE pass; the top-k threshold
is then read off the cumulative histogram on the host side of the jit
(tiny (64,) arithmetic).  Selection quality is bounded by bin granularity
(each bin spans a x2^(1/4) magnitude range with 1/4-exponent bins), which
keeps the selected count within ~19% of k — comparable to Algorithm 1's
[2k/3, 4k/3] accept band, at 1 pass instead of up to 5.

The histogram pass is the fused pipeline's pass A with its histogram
on (``ef_fused.fused_moments``): per-bin counts accumulate across the
sequential grid in revisited (8, 128) i32 tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BINS = 128        # 1/4-exponent bins covering 2^-16 .. 2^16
_LO_EXP = -16.0
_SCALE = 4.0      # bins per octave


def _bin_of(absx):
    """Bucket index of |x| (clamped into [0, BINS-1]); |x|=0 -> bin 0."""
    e = jnp.log2(jnp.maximum(absx, 2.0 ** (_LO_EXP - 1)))
    b = jnp.floor((e - _LO_EXP) * _SCALE)
    return jnp.clip(b, 0, BINS - 1).astype(jnp.int32)


def bin_lower_edge(b):
    """Magnitude lower edge of bin b (inverse of _bin_of)."""
    return 2.0 ** (b / _SCALE + _LO_EXP)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def abs_histogram(x2d: jax.Array, *, block: int = 2048,
                  interpret: bool = True) -> jax.Array:
    """(BINS,) histogram of |x| magnitude buckets over (nblocks, block)."""
    from repro.kernels.ef_fused.fused_moments import fused_moments

    return fused_moments(x2d, None, block=block, with_hist=True,
                         interpret=interpret,
                         backend="interpret" if interpret else "mosaic")[3]
