"""Sparsification operator zoo (paper §1, §3.3 and baselines §4.5).

Every compressor maps a flat vector ``u = g + e`` (stochastic gradient
accumulated with the error-feedback residual, Eq. 2) to a fixed-capacity
sparse ``(values, indices)`` pair — see ``codec.py`` for the encoding.

Implemented operators:

=============  ==========================================  ================
name           selection rule                              k_cap
=============  ==========================================  ================
``topk``       exact top-k by |u| (lax.top_k / sort)       k
``randk``      uniform random k (Gumbel-top-k trick)       k
``gaussiank``  paper Algorithm 1: Gaussian-ppf threshold   ceil(4k/3)
               + ≤4 refinement steps (band [2k/3, 4k/3])
``dgck``       DGC (Lin et al. 2018): sampled-threshold    k
               candidates, exact top-k among candidates
``trimmedk``   RedSync (Fang et al. 2019): mean→max        2k
               threshold bisection, accepts over-selection
``rtopk``      rTop-k (Barnes et al. 2020): strided        k
               r-sample, exact top-k WITHIN the sample
``none``       dense pass-through (Dense-SGD baseline)     d
=============  ==========================================  ================

All functions are jit-safe (static shapes, lax control flow only).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.scipy.stats import norm

from repro.core import codec


class CompressorSpec(NamedTuple):
    name: str
    select: Callable  # (u, k, key) -> (values, indices)
    k_cap: Callable[[int, int], int]  # (k, d) -> capacity
    needs_key: bool = False
    # runs Algorithm 1's refinement toward the band [2k/3, 4k/3]: its
    # kept count can end under the band (``ef_leaves_under_band``)
    banded: bool = False


# ---------------------------------------------------------------------------
# Exact Top-k
# ---------------------------------------------------------------------------

@jax.named_scope("ef.select")
def topk_select(u: jax.Array, k: int, key: Optional[jax.Array] = None):
    """Exact ``Top_k``: the k largest |u| coordinates (paper Eq. 3 context)."""
    _, idx = jax.lax.top_k(jnp.abs(u), k)
    idx = idx.astype(jnp.int32)
    return u[idx], idx


# ---------------------------------------------------------------------------
# Rand-k
# ---------------------------------------------------------------------------

@jax.named_scope("ef.select")
def randk_select(u: jax.Array, k: int, key: jax.Array):
    """``Rand_k``: k uniform indices without replacement (Gumbel-top-k)."""
    z = jax.random.uniform(key, u.shape)
    _, idx = jax.lax.top_k(z, k)
    idx = idx.astype(jnp.int32)
    return u[idx], idx


# ---------------------------------------------------------------------------
# Gaussian-k (paper Algorithm 1)
# ---------------------------------------------------------------------------

def gaussian_threshold(u: jax.Array, k: int, refine_iters: int = 4,
                       two_sided: bool = False):
    """Estimate the |u|-threshold selecting ~k elements (Algorithm 1 lines 2-13).

    ``two_sided=False`` is the paper-faithful version: ``p = 1 - k/d`` on the
    (μ, σ) normal fit — which over-selects ~2k for a centered distribution and
    relies on the refinement loop.  ``two_sided=True`` is a beyond-paper
    correction using ``p = 1 - k/(2d)`` so the first guess is already ≈ k.
    """
    d = u.shape[0]
    mu = jnp.mean(u)
    sigma = jnp.std(u) + 1e-12
    p = 1.0 - (k / (2.0 * d) if two_sided else k / d)
    thres = jnp.abs(norm.ppf(p, mu, sigma))

    lo = jnp.asarray(2.0 * k / 3.0, u.dtype)
    hi = jnp.asarray(4.0 * k / 3.0, u.dtype)
    abs_u = jnp.abs(u)

    def body(_, carry):
        thres, done = carry
        est = jnp.sum((abs_u > thres).astype(jnp.float32))
        new = jnp.where(est < lo, 0.5 * thres,
                        jnp.where(est > hi, 1.5 * thres, thres))
        in_band = (est >= lo) & (est <= hi)
        # once in band, stop moving (paper's `break`)
        thres = jnp.where(done, thres, new)
        return thres, done | in_band

    thres, _ = jax.lax.fori_loop(0, refine_iters, body, (thres, jnp.bool_(False)))
    return thres


@jax.named_scope("ef.select")
def gaussiank_select(u: jax.Array, k: int, key: Optional[jax.Array] = None,
                     refine_iters: int = 4, two_sided: bool = False):
    """``Gaussian_k`` (paper Algorithm 1): threshold + fixed-capacity compact."""
    k_cap = gaussiank_cap(k, u.shape[0])
    thres = gaussian_threshold(u, k, refine_iters, two_sided)
    mask = jnp.abs(u) > thres
    return codec.compact_by_mask(u, mask, k_cap)


def gaussiank_cap(k: int, d: int) -> int:
    # accept band upper edge (4k/3) — Algorithm 1 stops inside the band.
    return min(d, int(math.ceil(4.0 * k / 3.0)))


# ---------------------------------------------------------------------------
# DGC-k (hierarchical sampling, Lin et al. 2018)
# ---------------------------------------------------------------------------

def _strided_sample(key, d: int, s: int) -> jax.Array:
    """``s`` distinct indices in ``[0, d)``: a random-phase systematic
    sample.  Drawing with replacement (``jax.random.randint``) repeats
    indices — the effective sample shrinks and the estimated threshold
    biases high, under-selecting; a stride of ``d // s`` keeps the draw
    O(s), duplicate-free and uniformly spread over the vector."""
    stride = max(1, d // s)
    offset = jax.random.randint(key, (), 0, d)
    return (offset + stride * jnp.arange(s, dtype=jnp.int32)) % d


@jax.named_scope("ef.select")
def dgck_select(u: jax.Array, k: int, key: jax.Array, sample_ratio: float = 0.01):
    """``DGC_k``: estimate threshold from a random sample, gather candidates
    above it, then exact top-k among the candidates (two small top-k calls
    instead of one huge one)."""
    d = u.shape[0]
    s = max(k, int(math.ceil(sample_ratio * d)))
    s = min(s, d)
    # bias the sampled threshold low (x1.5) so candidates over-cover k and the
    # exact top-k pass trims — plain k*s/d has huge variance when it rounds to 1
    ks = max(1, min(s, int(math.ceil(1.5 * k * s / d))))
    samp = jnp.abs(u[_strided_sample(key, d, s)])
    sv, _ = jax.lax.top_k(samp, ks)
    thres = sv[-1]
    # candidates above the sampled threshold, capped at 2k
    cand_cap = min(d, 2 * k)
    cvals, cidx = codec.compact_by_mask(u, jnp.abs(u) >= thres, cand_cap)
    # exact top-k among candidates (sentinel slots have value 0)
    _, sel = jax.lax.top_k(jnp.abs(cvals), k)
    return cvals[sel], cidx[sel]


# ---------------------------------------------------------------------------
# rTop-k (statistical estimation, Barnes et al. 2020)
# ---------------------------------------------------------------------------


def rtopk_sample_size(k: int, d: int, sample_mult: float = 4.0) -> int:
    """Static sample width ``r = clip(ceil(sample_mult·k), k, d)``.

    A compile-time constant like :func:`gaussiank_cap`: the sample must
    cover at least ``k`` coordinates (the in-sample top-k needs that
    many candidates) and never more than the vector itself.
    """
    return max(k, min(d, int(math.ceil(sample_mult * k))))


@jax.named_scope("ef.select")
def rtopk_select(u: jax.Array, k: int, key: jax.Array,
                 sample_mult: float = 4.0):
    """``rTop_k`` (Barnes et al. 2020, arXiv:2005.10761): draw a random
    ``r``-coordinate sample, then exact top-k *within the sample*.

    For the near-Gaussian gradient distributions the paper measures
    (§3-§4), the sample's order statistics estimate the full vector's,
    so the in-sample top-k approaches true Top-k at a selection cost of
    ``O(r)`` instead of ``O(d)``.  The sample reuses the DGC strided
    machinery (:func:`_strided_sample`) — duplicate-free and uniformly
    spread, so the returned indices obey the codec contract with no
    sentinel padding: exactly ``k`` distinct pairs.
    """
    d = u.shape[0]
    r = rtopk_sample_size(k, d, sample_mult)
    sidx = _strided_sample(key, d, r).astype(jnp.int32)
    svals = u[sidx]
    _, sel = jax.lax.top_k(jnp.abs(svals), k)
    return svals[sel], sidx[sel]


def rtopk_cap(k: int, d: int) -> int:
    # the in-sample top-k returns exactly k duplicate-free pairs
    return min(d, k)


# ---------------------------------------------------------------------------
# Trimmed-k (RedSync, Fang et al. 2019)
# ---------------------------------------------------------------------------

@jax.named_scope("ef.select")
def trimmedk_select(u: jax.Array, k: int, key: Optional[jax.Array] = None,
                    iters: int = 16):
    """``Trimmed_k``: bisect a threshold between mean(|u|) and max(|u|).

    RedSync accepts thresholds selecting more than k elements (the paper
    notes it can heavily over-select); we cap the compaction at 2k.
    """
    abs_u = jnp.abs(u)
    lo = jnp.mean(abs_u)
    hi = jnp.max(abs_u)
    k_f = jnp.asarray(float(k), u.dtype)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        est = jnp.sum((abs_u > mid).astype(jnp.float32))
        # too many selected -> raise threshold; too few -> lower it
        lo = jnp.where(est > 1.25 * k_f, mid, lo)
        hi = jnp.where(est < k_f, mid, hi)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    thres = lo
    return codec.compact_by_mask(u, abs_u > thres, min(u.shape[0], 2 * k))


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

@jax.named_scope("ef.select")
def histk_select(u: jax.Array, k: int, key: Optional[jax.Array] = None):
    """``Hist_k`` (beyond-paper): one-pass exponent-histogram threshold +
    blocked compaction — 2 passes over u total, no refinement loop.  Reuses
    the Pallas kernel pipeline (interpret mode on CPU)."""
    from repro.kernels.histk import histk_select_kernel
    return histk_select_kernel(u, k)


_REGISTRY = {
    "topk": CompressorSpec("topk", topk_select, lambda k, d: k),
    "randk": CompressorSpec("randk", randk_select, lambda k, d: k, needs_key=True),
    "gaussiank": CompressorSpec("gaussiank", gaussiank_select, gaussiank_cap,
                                banded=True),
    "gaussiank2": CompressorSpec(
        "gaussiank2", partial(gaussiank_select, two_sided=True), gaussiank_cap,
        banded=True),
    "dgck": CompressorSpec("dgck", dgck_select, lambda k, d: k, needs_key=True),
    "trimmedk": CompressorSpec(
        "trimmedk", trimmedk_select, lambda k, d: min(d, 2 * k)),
    "histk": CompressorSpec("histk", histk_select, gaussiank_cap),
    "rtopk": CompressorSpec("rtopk", rtopk_select, rtopk_cap,
                            needs_key=True),
}


def get_compressor(name: str) -> CompressorSpec:
    if name not in _REGISTRY:
        raise KeyError(f"unknown compressor {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def available() -> list[str]:
    return sorted(_REGISTRY)
