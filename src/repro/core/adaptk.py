"""Adaptive layer-wise density scheduling (beyond-paper; DESIGN.md §9).

The paper's §3-§4 observation is that gradient magnitudes are
near-Gaussian and their distribution drifts during training, so a fixed
global density ``k/d`` is the wrong operating point — the right ``k``
differs per layer and per step.  Following Adaptive Top-K SGD (Ruan et
al. 2022) and rTop-k (Barnes et al. 2020), this module steers a *global*
per-step element budget ``K_total`` across gradient leaves from the
per-leaf moments the fused EF pipeline's pass A already computes (sum,
sum-of-squares, abs-max of ``u = g + e`` — ``kernels/ef_fused``), so the
adaptation signal costs no extra HBM traffic.  A DGC-style exponential
density warmup (Lin et al. 2018 §3.2; ``optim/schedules.py``) scales the
global budget early in training.

Shape discipline (the whole point of the design): the per-leaf budget
``k`` becomes a *traced* per-step scalar, but every shape-bearing
quantity — the codec capacity ``k_cap``, staging widths, wire volume —
stays a compile-time constant derived from the policy's per-leaf
*ceiling* clamp.  ``allocate`` is budget-exact: the integer per-leaf
budgets sum to ``K_eff = clip(K_total, sum(floors), sum(ceilings))``
every step (asserted by tests/test_properties.py).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import codec
from repro.core.compressors import (CompressorSpec, _strided_sample,
                                    gaussian_threshold, rtopk_sample_size)

POLICIES = ("uniform", "variance", "absmax")

# global-budget controllers (DESIGN.md §12): "none" keeps K_total at the
# configured ratio x warmup schedule; "normdecay" (Adaptive Top-K, Ruan
# et al. 2022) additionally scales it by the estimated gradient-norm
# decay — an EMA of the pmean'd pass-A second moment over its frozen
# first observation.
GLOBALK_POLICIES = ("none", "normdecay")

# compressors with a dynamic-k (traced per-step budget) selection path:
# threshold-style rules take k as a plain scalar in the threshold math;
# topk/randk/rtopk rank at the static capacity and mask ranks >= k.
# dgck and trimmedk bake k into static candidate/sample shapes and stay
# fixed-k.
DYNAMIC_COMPRESSORS = ("topk", "randk", "rtopk", "gaussiank", "gaussiank2",
                       "histk")


class DensityPolicy(NamedTuple):
    """How the global element budget is spread across leaves per step.

    ``policy``       allocation weights: "uniform" (leaf size —
                     recovers the fixed-k split, but budget-exact),
                     "variance" (total centered energy ``d·Var[u]``) or
                     "absmax" (``d·max|u|``).
    ``floor_mult``   per-leaf floor = ``ceil(floor_mult · k_uniform)``
                     (conservation: no leaf is starved below it).
    ``ceil_mult``    per-leaf ceiling multiplier; together with
                     ``warmup_mult`` it fixes the static codec capacity
                     ``k_cap`` (staging bounds — DESIGN.md §9).
    ``ema``          EMA factor over the allocation signal (0 =
                     stateless, use this step's moments directly).
    ``warmup_steps``/``warmup_mult``  DGC-style exponential density
                     warmup: the global budget starts at
                     ``warmup_mult × K_total`` and decays geometrically
                     to ``1×`` over ``warmup_steps`` steps.
    ``global_policy``/``global_ema``/``global_floor``  convergence-aware
                     global-k controller (:func:`global_scale`,
                     DESIGN.md §12): ``"normdecay"`` scales ``K_total``
                     by ``clip(sqrt(EMA[Σu²] / Σu²_first),
                     global_floor, 1)``.  The scale never exceeds 1, so
                     the ceiling clamp (and with it every static codec
                     capacity) is untouched.
    """
    policy: str = "variance"
    floor_mult: float = 0.25
    ceil_mult: float = 4.0
    ema: float = 0.0
    warmup_steps: int = 0
    warmup_mult: float = 1.0
    global_policy: str = "none"
    global_ema: float = 0.9
    global_floor: float = 0.25

    @property
    def cap_mult(self) -> float:
        """Static ceiling multiplier: the warmup peak must fit under the
        per-leaf ceiling or the budget clip would silently flatten it."""
        return max(self.ceil_mult, self.warmup_mult)


def make_policy(policy: str = "variance", *, floor_mult: float = 0.25,
                ceil_mult: float = 4.0, ema: float = 0.0,
                warmup_steps: int = 0,
                warmup_mult: float = 1.0,
                global_policy: str = "none",
                global_ema: float = 0.9,
                global_floor: float = 0.25) -> DensityPolicy:
    """Validated :class:`DensityPolicy` constructor."""
    if policy not in POLICIES:
        raise ValueError(f"unknown density policy {policy!r}; have {POLICIES}")
    if not 0.0 < floor_mult <= 1.0:
        raise ValueError(f"floor_mult must be in (0, 1], got {floor_mult}")
    if ceil_mult < 1.0:
        raise ValueError(f"ceil_mult must be >= 1, got {ceil_mult}")
    if not 0.0 <= ema < 1.0:
        raise ValueError(f"ema must be in [0, 1), got {ema}")
    if warmup_steps < 0 or warmup_mult < 1.0:
        raise ValueError("warmup_steps must be >= 0 and warmup_mult >= 1, "
                         f"got {warmup_steps}, {warmup_mult}")
    if global_policy not in GLOBALK_POLICIES:
        raise ValueError(f"unknown global-k policy {global_policy!r}; "
                         f"have {GLOBALK_POLICIES}")
    if not 0.0 <= global_ema < 1.0:
        raise ValueError(f"global_ema must be in [0, 1), got {global_ema}")
    if not 0.0 < global_floor <= 1.0:
        raise ValueError(f"global_floor must be in (0, 1], got "
                         f"{global_floor}")
    return DensityPolicy(policy, float(floor_mult), float(ceil_mult),
                         float(ema), int(warmup_steps), float(warmup_mult),
                         global_policy, float(global_ema),
                         float(global_floor))


def supports_dynamic(spec: CompressorSpec) -> bool:
    return spec.name in DYNAMIC_COMPRESSORS


# ---------------------------------------------------------------------------
# static bounds and per-step budget
# ---------------------------------------------------------------------------


def leaf_bounds(d: int, ratio: float, policy: DensityPolicy):
    """Static ``(k_floor, k_ceil)`` clamp for a ``d``-element leaf.

    Both derive from the fixed-k budget ``k_u = ceil(ratio·d)``; the
    ceiling uses :attr:`DensityPolicy.cap_mult` so the warmup peak fits.
    The ceiling is what every static capacity (codec ``k_cap``, staging
    ``bcap``, wire volume) is sized from.
    """
    k_u = max(1, math.ceil(ratio * d))
    k_lo = max(1, min(d, math.ceil(policy.floor_mult * k_u)))
    k_hi = max(k_lo, min(d, math.ceil(policy.cap_mult * k_u)))
    return k_lo, k_hi


def budget(dims: Sequence[int], ratio: float, policy: DensityPolicy,
           step=None) -> jax.Array:
    """Global element budget ``K_total`` for one step (int32 scalar).

    ``round(ratio · d_total)`` scaled by the DGC warmup multiplier when
    the policy has one (needs ``step``).  Callers pass the result to
    :func:`allocate`, which clips it into ``[sum(floors),
    sum(ceilings)]`` — the clipped value ``K_eff`` is what budget
    exactness is asserted against.
    """
    base = float(ratio) * float(sum(dims))
    if policy.warmup_steps > 0:
        if step is None:
            raise ValueError("density warmup needs the step index; pass "
                             "step= to aggregate_compressed / budget()")
        from repro.optim.schedules import density_warmup
        mult = density_warmup(policy.warmup_mult, policy.warmup_steps)(step)
    else:
        mult = 1.0
    return jnp.round(base * mult).astype(jnp.int32)


# ---------------------------------------------------------------------------
# allocation signal (from the fused pass-A moments)
# ---------------------------------------------------------------------------


def leaf_signal(policy_name: str, d: int, s, sq, mx) -> jax.Array:
    """Allocation weight of one leaf from its pass-A moments of ``u``.

    ``s = sum(u)``, ``sq = sum(u²)``, ``mx = max|u|`` — exactly what
    ``kernels/ef_fused.fused_pass_a`` (or one jnp reduction) emits.
    Weights are relative, so any positive rescaling is equivalent.
    """
    if policy_name == "uniform":
        return jnp.float32(d)
    if policy_name == "variance":
        # total centered energy: sum(u²) − sum(u)²/d == d·Var[u]
        return jnp.maximum(jnp.float32(sq) - jnp.float32(s) ** 2 / d, 0.0)
    if policy_name == "absmax":
        return jnp.float32(d) * jnp.float32(mx)
    raise ValueError(f"unknown density policy {policy_name!r}; "
                     f"have {POLICIES}")


# ---------------------------------------------------------------------------
# controller state (EMA over the signal — lives in TrainState)
# ---------------------------------------------------------------------------


def init_controller_state(n_leaves: int, global_k: bool = False) -> dict:
    """Zero EMA state: ``signal`` is the smoothed per-leaf weight vector,
    ``count`` gates the cold start (first step uses the fresh signal).

    ``global_k`` additionally allocates the :func:`global_scale`
    controller scalars: ``gnorm`` (the EMA'd total second moment) and
    ``gnorm0`` (its frozen first observation, the norm-decay reference).
    Both self-seed from their first positive observation, so zero-filled
    state — fresh or migrated from a pre-globalk checkpoint — is exact.
    """
    state = {"signal": jnp.zeros((n_leaves,), jnp.float32),
             "count": jnp.zeros((), jnp.int32)}
    if global_k:
        state["gnorm"] = jnp.zeros((), jnp.float32)
        state["gnorm0"] = jnp.zeros((), jnp.float32)
    return state


def blend_signal(state: Optional[dict], fresh: jax.Array, ema: float):
    """EMA-smooth the allocation signal; returns ``(blended, new_state)``.

    ``state=None`` runs stateless (fresh signal, no new state).  With a
    state, the first observation seeds the EMA (no zero-init bias).
    Keys beyond ``signal``/``count`` (the :func:`global_scale` scalars)
    pass through untouched for their own update.
    """
    if state is None:
        return fresh, None
    if ema > 0.0:
        seeded = state["count"] > 0
        blended = jnp.where(seeded,
                            ema * state["signal"] + (1.0 - ema) * fresh,
                            fresh)
    else:
        blended = fresh
    return blended, {**state, "signal": blended,
                     "count": state["count"] + 1}


# ---------------------------------------------------------------------------
# convergence-aware global-k controller (DESIGN.md §12)
# ---------------------------------------------------------------------------


def global_scale(state: Optional[dict], sq_total, policy: DensityPolicy):
    """Global-budget scale from the estimated gradient-norm decay.

    ``sq_total`` is the pmean'd total pass-A second moment ``Σ u²``
    across all leaves — the squared gradient-norm estimate the fused
    pipeline already streams.  The ``"normdecay"`` controller (Adaptive
    Top-K, Ruan et al. 2022) EMAs it (``global_ema``), freezes the first
    observation as the reference, and returns

        ``scale = clip(sqrt(EMA[Σu²] / Σu²_first), global_floor, 1)``

    — as the norm decays toward convergence, fewer coordinates carry the
    gradient's mass and the global element budget shrinks with it.  The
    scale never exceeds 1, so every static shape sized from the ceiling
    clamp stays valid.  Returns ``(scale, state_updates)``; merge the
    updates into the controller state (the caller owns the dict).  Both
    scalars self-seed from the first positive observation, which also
    makes zero-filled legacy-checkpoint state exact.
    """
    if policy.global_policy == "none":
        return jnp.float32(1.0), {}
    if state is None or "gnorm" not in state:
        raise ValueError(
            f"global-k policy {policy.global_policy!r} is stateful; "
            "allocate the controller scalars via "
            "init_controller_state(n, global_k=True) (init_train_state "
            "does this when density_policy.global_policy is set)")
    n = jnp.maximum(jnp.asarray(sq_total, jnp.float32), 0.0)
    sm = jnp.where(state["gnorm"] > 0.0,
                   policy.global_ema * state["gnorm"]
                   + (1.0 - policy.global_ema) * n,
                   n)
    ref = jnp.where(state["gnorm0"] > 0.0, state["gnorm0"], n)
    ratio = jnp.where(ref > 0.0, sm / ref, 1.0)
    scale = jnp.clip(jnp.sqrt(ratio), policy.global_floor, 1.0)
    return scale, {"gnorm": sm, "gnorm0": ref}


def scale_budget(K, scale):
    """Apply a :func:`global_scale` factor to an int32 element budget."""
    return jnp.round(K.astype(jnp.float32) * scale).astype(jnp.int32)


# ---------------------------------------------------------------------------
# budget-exact integer apportionment
# ---------------------------------------------------------------------------


def allocate(K_total, weights, lo, hi, *, bisect_iters: int = 48):
    """Split ``K_total`` elements over leaves, proportional to ``weights``
    under per-leaf ``[lo, hi]`` clamps — budget-EXACT.

    Returns ``(k, K_eff)`` int32 with ``sum(k) == K_eff ==
    clip(K_total, sum(lo), sum(hi))`` exactly, ``lo <= k <= hi``
    element-wise.  Deterministic and jit-safe: a fixed-iteration
    bisection finds the water-filling scale ``λ`` with
    ``sum(clip(λ·w, lo, hi)) == K_eff`` (monotone in ``λ``), the floored
    integer solution is then fixed up one element at a time by
    largest-remainder rank (stable argsort — ties break by leaf order),
    which also absorbs any float error of the bisection.  All-zero
    weights fall back to capacity-proportional; zero-weight leaves stay
    at their floor until every positive-weight leaf hits its ceiling
    (a vanishing tie-break epsilon keeps ``λ`` finite).

    Budgets are int32 — fine up to ~2·10⁹ total elements on the wire,
    far above any per-step sparse budget this repo configures.
    """
    lo = jnp.asarray(lo, jnp.int32)
    hi = jnp.asarray(hi, jnp.int32)
    if lo.shape != hi.shape or lo.ndim != 1:
        raise ValueError(f"lo/hi must be matching 1-D, got {lo.shape} "
                         f"{hi.shape}")
    K_eff = jnp.clip(jnp.asarray(K_total, jnp.int32),
                     jnp.sum(lo), jnp.sum(hi))
    cap = (hi - lo) > 0
    w = jnp.maximum(jnp.asarray(weights, jnp.float32), 0.0)
    w = jnp.where(jnp.sum(w) > 0.0, w, (hi - lo).astype(jnp.float32))
    w = w / jnp.maximum(jnp.max(w), 1e-30)
    w = w + 1e-6 * cap.astype(jnp.float32)   # λ stays finite w/ capacity
    lo_f, hi_f = lo.astype(jnp.float32), hi.astype(jnp.float32)
    Kf = K_eff.astype(jnp.float32)

    lam_hi = jnp.max(jnp.where(cap, hi_f / jnp.maximum(w, 1e-30), 0.0)) + 1.0

    def bis(_, ab):
        a, b = ab
        m = 0.5 * (a + b)
        f = jnp.sum(jnp.clip(m * w, lo_f, hi_f))
        return jnp.where(f < Kf, m, a), jnp.where(f < Kf, b, m)

    _, lam = jax.lax.fori_loop(0, bisect_iters, bis, (0.0, lam_hi))
    kc = jnp.clip(lam * w, lo_f, hi_f)
    k = jnp.clip(jnp.floor(kc).astype(jnp.int32), lo, hi)
    frac = kc - jnp.floor(kc)
    prio = frac + w  # largest remainder, weight-then-leaf-order tie-break

    def fix_cond(carry):
        kk, it = carry
        return (jnp.sum(kk) != K_eff) & (it < 4096)

    def fix_body(carry):
        kk, it = carry
        rem = K_eff - jnp.sum(kk)
        can_g = kk < hi
        rg = jnp.argsort(jnp.argsort(jnp.where(can_g, -prio, jnp.inf)))
        kk = kk + (can_g & (rg < jnp.maximum(rem, 0))).astype(jnp.int32)
        can_t = kk > lo
        rt = jnp.argsort(jnp.argsort(jnp.where(can_t, prio, jnp.inf)))
        kk = kk - (can_t & (rt < jnp.maximum(-rem, 0))).astype(jnp.int32)
        return kk, it + 1

    k, _ = jax.lax.while_loop(fix_cond, fix_body, (k, jnp.int32(0)))
    return k, K_eff


# ---------------------------------------------------------------------------
# dynamic-k selection (traced budget, static capacity)
# ---------------------------------------------------------------------------


@jax.named_scope("ef.select")
def select_dynamic(spec: CompressorSpec, u: jax.Array, k, k_cap: int,
                   key=None):
    """Fixed-capacity selection with a *traced* per-step budget ``k``.

    Returns sentinel-padded ``(values, indices)`` of static shape
    ``(k_cap,)`` per the ``core.codec`` contract; ``k`` is clamped to
    ``[1, k_cap]`` by construction at the call sites (the allocator's
    ceiling clamp is what ``k_cap`` was sized from).  Threshold-style
    compressors take ``k`` straight into their threshold math;
    topk/randk rank at the static capacity and sentinel out ranks
    ``>= k``.  Raises for compressors without a dynamic path
    (``DYNAMIC_COMPRESSORS``).
    """
    name = spec.name
    if name not in DYNAMIC_COMPRESSORS:
        raise ValueError(
            f"compressor {name!r} bakes its per-step budget k into static "
            f"sample/candidate shapes, so it has no dynamic-k (traced "
            f"budget) path; adaptive density policies support "
            f"{DYNAMIC_COMPRESSORS}.  Run {name!r} fixed-k instead: drop "
            f"--density-policy on the CLI (density_policy=None in "
            f"aggregate_compressed / make_train_step).")
    d = u.shape[0]
    k_cap = min(k_cap, d)
    if name in ("topk", "randk"):
        score = jnp.abs(u) if name == "topk" else jax.random.uniform(
            key, u.shape)
        _, idx = jax.lax.top_k(score, k_cap)
        idx = idx.astype(jnp.int32)
        keep = jnp.arange(k_cap, dtype=jnp.int32) < k
        values = jnp.where(keep, u[idx], jnp.zeros((), u.dtype))
        indices = jnp.where(keep, idx, codec.SENTINEL)
        return values, indices
    if name == "rtopk":
        # static sample geometry from the capacity (= the allocator's
        # ceiling), in-sample rank at k_cap, sentinel out ranks >= k
        r = rtopk_sample_size(k_cap, d)
        sidx = _strided_sample(key, d, r).astype(jnp.int32)
        svals = u[sidx]
        _, sel = jax.lax.top_k(jnp.abs(svals), k_cap)
        keep = jnp.arange(k_cap, dtype=jnp.int32) < k
        values = jnp.where(keep, svals[sel], jnp.zeros((), u.dtype))
        indices = jnp.where(keep, sidx[sel], codec.SENTINEL)
        return values, indices
    if name in ("gaussiank", "gaussiank2"):
        thres = gaussian_threshold(u, k, two_sided=(name == "gaussiank2"))
        return codec.compact_by_mask(u, jnp.abs(u) > thres, k_cap)
    # histk: jnp histogram threshold (reference path; the fused pipeline
    # reads the pass-A histogram instead — kernels/ef_fused)
    from repro.kernels.histk.hist import BINS, _bin_of
    from repro.kernels.histk.ops import threshold_from_histogram
    h = jnp.zeros((BINS,), jnp.float32).at[_bin_of(jnp.abs(u))].add(1.0)
    thres = threshold_from_histogram(h, k)
    return codec.compact_by_mask(u, jnp.abs(u) > thres, k_cap)
