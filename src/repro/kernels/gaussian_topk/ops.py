"""Jitted Gaussian_k selection pipeline built from the Pallas kernels.

Pipeline (paper Algorithm 1, TPU-native):
  1. ``moments``            — one-pass mean/std                (1 HBM read)
  2. ppf threshold + ``count_gt`` refinement loop (≤4 passes)
  3. ``threshold_compact``  — one-hot-matmul block compaction  (1 HBM read)
  4. tiny assembly of the per-block staging buffers into the fixed
     ``(k_cap,)`` codec (operates on ~k-sized arrays, XLA scatter).

Total: ≤6 linear passes over u and NO sort — vs. O(d log d) sort networks
for exact top-k.  Per-block staging overflow is dropped and re-absorbed
by error feedback (DESIGN.md §3).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.scipy.stats import norm

from repro.core.codec import SENTINEL
from repro.core.compressors import gaussiank_cap
from repro.kernels.gaussian_topk.count_gt import count_gt
from repro.kernels.gaussian_topk.threshold_compact import threshold_compact
from repro.kernels.moments.ops import mean_std_absmax


def default_bcap(k_cap: int, d: int, block: int) -> int:
    """Per-block staging width: 4x the expected per-block selection, >=64."""
    expected = k_cap * block / max(d, 1)
    return int(min(block, max(64, 8 * math.ceil(expected * 4 / 8))))


@functools.partial(jax.jit, static_argnames=("k", "block", "refine_iters",
                                             "two_sided", "interpret"))
def gaussian_threshold_kernel(u: jax.Array, k: int, *, block: int = 2048,
                              refine_iters: int = 4, two_sided: bool = False,
                              interpret: bool = True) -> jax.Array:
    """Kernel-backed threshold estimate (Algorithm 1 lines 2-13)."""
    d = u.shape[0]
    pad = (-d) % block
    x2d = jnp.pad(u, (0, pad)).reshape(-1, block)
    mean, std, _ = mean_std_absmax(u, block=block, interpret=interpret)
    p = 1.0 - (k / (2.0 * d) if two_sided else k / d)
    thres = jnp.maximum(jnp.abs(norm.ppf(p, mean, std + 1e-12)), 0.0)

    lo = 2.0 * k / 3.0
    hi = 4.0 * k / 3.0

    def body(_, carry):
        thres, done = carry
        est = count_gt(x2d, thres, block=block, interpret=interpret)
        est = est.astype(jnp.float32)
        new = jnp.where(est < lo, 0.5 * thres,
                        jnp.where(est > hi, 1.5 * thres, thres))
        in_band = (est >= lo) & (est <= hi)
        thres = jnp.where(done, thres, new)
        return thres, done | in_band

    thres, _ = jax.lax.fori_loop(0, refine_iters, body,
                                 (thres, jnp.bool_(False)))
    return thres


def assemble_staging(vals: jax.Array, offs: jax.Array, cnts: jax.Array,
                     d: int, k_cap: int, *, block: int = 2048,
                     out_dtype=jnp.float32):
    """Assemble per-block staging buffers into the fixed ``(k_cap,)`` codec.

    Operates on the ~k-sized ``(nblocks, bcap)`` staging layout written
    by ``threshold_compact`` (and by the fused ``compact_residual``
    kernel, which shares this assembly): per-block entries land at the
    global slot ``cumsum(min(cnt, bcap)) + local``, anything past
    ``k_cap`` is dropped.
    """
    nblocks, bcap = vals.shape
    enc = jnp.minimum(cnts, bcap)                       # encoded per block
    base = jnp.cumsum(enc) - enc                        # exclusive prefix
    j = jnp.arange(bcap, dtype=jnp.int32)[None, :]
    gidx = jnp.arange(nblocks, dtype=jnp.int32)[:, None] * block + offs
    valid = (j < enc[:, None]) & (offs != SENTINEL) & (gidx < d)
    gslot = base[:, None] + j
    slot = jnp.where(valid & (gslot < k_cap), gslot, k_cap)
    values = jnp.zeros((k_cap + 1,), jnp.float32).at[slot.ravel()].set(
        vals.ravel(), mode="drop")
    indices = jnp.full((k_cap + 1,), SENTINEL, jnp.int32).at[slot.ravel()].set(
        gidx.ravel(), mode="drop")
    return values[:k_cap].astype(out_dtype), indices[:k_cap]


@functools.partial(jax.jit, static_argnames=("k_cap", "block", "bcap",
                                             "interpret"))
@jax.named_scope("ef.compact")
def select_by_threshold(u: jax.Array, thres: jax.Array, k_cap: int, *,
                        block: int = 2048, bcap: int | None = None,
                        interpret: bool = True):
    """Compact |u| > thres into the fixed (k_cap,) codec via the Pallas
    block-compaction kernel + small assembly."""
    d = u.shape[0]
    pad = (-d) % block
    x2d = jnp.pad(u, (0, pad)).reshape(-1, block)
    if bcap is None:
        bcap = default_bcap(k_cap, d, block)
    thres = jnp.maximum(jnp.asarray(thres, jnp.float32), 0.0)
    vals, offs, cnts = threshold_compact(x2d, thres, bcap=bcap, block=block,
                                         interpret=interpret)
    return assemble_staging(vals, offs, cnts, d, k_cap, block=block,
                            out_dtype=u.dtype)


@functools.partial(jax.jit, static_argnames=("k", "block", "refine_iters",
                                             "two_sided", "interpret"))
def gaussiank_select_kernel(u: jax.Array, k: int, *, block: int = 2048,
                            refine_iters: int = 4, two_sided: bool = False,
                            interpret: bool = True):
    """Full kernel-backed ``Gaussian_k`` compressor (drop-in for
    ``core.compressors.gaussiank_select``)."""
    thres = gaussian_threshold_kernel(u, k, block=block,
                                      refine_iters=refine_iters,
                                      two_sided=two_sided, interpret=interpret)
    k_cap = gaussiank_cap(k, u.shape[0])
    return select_by_threshold(u, thres, k_cap, block=block,
                               interpret=interpret)
