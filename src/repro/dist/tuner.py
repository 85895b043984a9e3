"""Topology-aware wire-strategy auto-tuner (DESIGN.md §14).

``--strategy auto`` asks: which wire pattern moves this layout's step
fastest on *this* interconnect?  The old bandwidth-only model could not
answer — gTop-k's log2(W) latency-bound rounds cost ~nothing on paper,
so it would always win.  The tuner prices every candidate in
:data:`~repro.core.compression.STRATEGIES` under three terms:

1. **alpha** — per-message dispatch latency.  Message counts come from
   the same closed forms the wire metrics use
   (``layout.collective_count``): one codec-pair event is
   :data:`MSGS_PER_PAIR` array messages (values + indices).  A joint
   all-gather is ONE dispatch paying the slowest participating axis's
   alpha; every gTop-k round is its own dispatch.
2. **beta** — bytes over each mesh axis divided by that axis's
   bandwidth.  Payloads come from ``layout.pair_bits`` and the ring
   decomposition of each collective (``strategy_wire_pairs`` totals).
3. **merge compute** — the *serialized* decode/merge work between
   rounds, priced against ``HardwareSpec.hbm_bw``.  This is the paper's
   Fig.-4 asymmetry applied to the wire: a gather strategy decodes all
   pairs once in one fused pass, while every gTop-k merge round
   re-selects an exact top-k over the full bucket (a sort-class pass,
   :data:`TOPK_PASSES_PER_LOG2D`·log2(d_row) sweeps); the hierarchical
   second-level compress is a threshold-based selection (cheap,
   :data:`COMPRESS_PASSES` sweeps).  Without this term the strategy
   choice could never flip back toward gathers on fast links.

The per-mesh-axis decision is encoded in the candidate set itself: for
a two-level mesh the four strategies enumerate the {gather, gtopk}
choices per level (``allgather`` = joint gather, ``hierarchical`` =
gather/gather + re-compress, ``hier_gtopk`` = gather inner / gtopk
across pods, ``gtopk`` = joint recursive doubling).  Exact-tie breaks
(e.g. ``hier_gtopk`` vs ``hierarchical`` at n_pods=2, where they are
the same algorithm) resolve by :data:`TIE_RANK` — the strategy that
generalizes better to deeper meshes wins the tie.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

from repro.dist.layout import BucketLayout, _log2_exact
from repro.launch.topo import Topology

__all__ = [
    "MSGS_PER_PAIR", "TOPK_PASSES_PER_LOG2D", "COMPRESS_PASSES",
    "TIE_RANK", "WirePrediction", "TunerDecision",
    "candidate_strategies", "predict_wire_time", "choose_strategy",
    "measure_wire_time", "measure_wire_pattern",
]

# one codec-pair exchange moves two arrays: values + indices
MSGS_PER_PAIR = 2

# merge-compute model (equivalent full sweeps of the dense bucket,
# priced at HardwareSpec.hbm_bw):
# exact top-k re-selection inside a gTop-k merge round — sort-class,
# scales with log2 of the row length (paper Fig. 4: exact selection is
# the expensive class)
TOPK_PASSES_PER_LOG2D = 0.5
# threshold-based second-level compress of the hierarchical family
# (read mean + residual, write residual — no sort)
COMPRESS_PASSES = 3.0

# exact-tie preference, best first: the two-level hybrid degenerates to
# plain hierarchical at n_pods=2 (identical wire and merge), and a
# W=2 gather ties a 1-round gtopk; prefer the member of each tie that
# scales better when the mesh deepens/widens under the same topology.
TIE_RANK = {"hier_gtopk": 0, "hierarchical": 1, "allgather": 2, "gtopk": 3}


def _is_pow2(n: int) -> bool:
    return n >= 1 and (n & (n - 1)) == 0


@dataclass(frozen=True)
class WirePrediction:
    """Predicted per-step wire cost of one strategy under a topology."""
    strategy: str
    wire_s: float                      # alpha + beta terms
    merge_s: float                     # serialized merge compute
    messages: int                      # collective dispatches x arrays
    bytes_on_wire: float               # per-worker payload total
    axis_wire_s: Tuple[Tuple[str, float], ...] = ()

    @property
    def total_s(self) -> float:
        return self.wire_s + self.merge_s

    def to_dict(self) -> dict:
        return {"strategy": self.strategy, "wire_s": self.wire_s,
                "merge_s": self.merge_s, "total_s": self.total_s,
                "messages": self.messages,
                "bytes_on_wire": self.bytes_on_wire,
                "axis_wire_s": dict(self.axis_wire_s)}


@dataclass(frozen=True)
class TunerDecision:
    strategy: str
    predictions: Tuple[WirePrediction, ...]   # sorted best-first
    topology: str = "default"

    @property
    def considered(self) -> Tuple[str, ...]:
        return tuple(p.strategy for p in self.predictions)

    @property
    def best(self) -> WirePrediction:
        return self.predictions[0]

    def to_dict(self) -> dict:
        return {"strategy": self.strategy, "topology": self.topology,
                "predictions": [p.to_dict() for p in self.predictions]}


def candidate_strategies(axis_sizes: Sequence[int]) -> Tuple[str, ...]:
    """Strategies valid on a mesh with these data-axis sizes (outermost
    first).  ``allgather`` always works; ``gtopk`` needs every axis to be
    a power of two (the XOR tree halves exactly); the two-level
    strategies need >= 2 data axes, and the hybrid additionally a
    power-of-two pod count."""
    sizes = [int(n) for n in axis_sizes]
    cands = ["allgather"]
    world = math.prod(sizes) if sizes else 1
    if world >= 2 and all(_is_pow2(n) for n in sizes):
        cands.append("gtopk")
    if len(sizes) > 1:
        cands.append("hierarchical")
        if _is_pow2(sizes[0]):
            cands.append("hier_gtopk")
    return tuple(cands)


def _ring_gather(axes, pair_bytes: float, topo: Topology):
    """(seconds, messages, bytes) of ONE joint ring all-gather of a
    codec pair over ``axes`` (outermost first).  Innermost axes move
    first; the accumulated block grows by the axis size at each level —
    axis i moves ``(n_i - 1) * block_i`` bytes.  One dispatch pays the
    slowest participating axis's alpha once per array message."""
    live = [(ax, n) for ax, n in axes if n > 1]
    if not live:
        return 0.0, 0, 0.0, {}
    alpha = max(topo.link(ax).alpha_s for ax, _ in live)
    per_axis: Dict[str, float] = {}
    t = MSGS_PER_PAIR * alpha
    moved_total = 0.0
    block = float(pair_bytes)
    for ax, n in reversed(live):
        moved = (n - 1) * block
        dt = moved / topo.link(ax).beta_Bps
        per_axis[ax] = per_axis.get(ax, 0.0) + dt
        t += dt
        moved_total += moved
        block *= n
    return t, MSGS_PER_PAIR, moved_total, per_axis


def _gtopk_rounds(axes, pair_bytes: float, topo: Topology):
    """(seconds, messages, bytes) of the recursive-doubling rounds over
    ``axes``: log2(n) rounds per axis, each its own dispatch of one
    codec pair."""
    t, msgs, moved = 0.0, 0, 0.0
    per_axis: Dict[str, float] = {}
    for ax, n in axes:
        if n <= 1:
            continue
        rounds = _log2_exact(int(n), f"axis {ax!r} size")
        link = topo.link(ax)
        dt = rounds * link.time_s(MSGS_PER_PAIR, pair_bytes)
        per_axis[ax] = per_axis.get(ax, 0.0) + dt
        t += dt
        msgs += rounds * MSGS_PER_PAIR
        moved += rounds * pair_bytes
    return t, msgs, moved, per_axis


def predict_wire_time(strategy: str, axes: Sequence[Tuple[str, int]],
                      pair_bytes: float, dense_bytes: float,
                      topo: Topology, *,
                      d_row: Optional[int] = None) -> WirePrediction:
    """Price one strategy's per-step wire stage on a mesh.

    ``axes``: data axes as ``(name, size)`` pairs, outermost (pod)
    first.  ``pair_bytes``: one worker's codec-pair payload
    (``layout.pair_bits/8``).  ``dense_bytes``: the decoded bucket
    (``model_size * d_row_total * itemsize``) — the unit of the merge-
    compute sweeps.  ``d_row`` sizes the top-k sort term (defaults to
    ``dense_bytes/4`` elements in one row-agnostic bucket).
    """
    live = [(ax, int(n)) for ax, n in axes]
    world = math.prod(n for _, n in live) if live else 1
    hbm = topo.hardware.hbm_bw
    d_eff = int(d_row) if d_row else max(2, int(dense_bytes // 4))
    sweep = dense_bytes / hbm                       # one full-bucket pass
    pair_pass = pair_bytes / hbm

    def decode_sum(n_pairs):
        # fused decode+sum of n pairs: one dense accumulation pass plus
        # the pair reads
        return sweep + n_pairs * pair_pass

    # exact top-k re-encode of a merge round (sort-class) vs the
    # threshold-based second-level compress (no sort)
    reencode = (2.0 + TOPK_PASSES_PER_LOG2D * math.log2(d_eff)) * sweep \
        + pair_pass
    round_merge = reencode + decode_sum(1)
    compress2 = COMPRESS_PASSES * sweep + pair_pass

    if strategy == "allgather":
        wire, msgs, moved, per_axis = _ring_gather(live, pair_bytes, topo)
        merge = decode_sum(world) if world > 1 else 0.0
    elif strategy == "gtopk":
        wire, msgs, moved, per_axis = _gtopk_rounds(live, pair_bytes, topo)
        rounds = sum(_log2_exact(n, "axis size") for _, n in live if n > 1)
        merge = (decode_sum(2) + (rounds - 1) * round_merge
                 if rounds else 0.0)
    elif strategy in ("hierarchical", "hier_gtopk"):
        if len(live) < 2:
            raise ValueError(f"{strategy} needs >= 2 data axes, got {live}")
        outer, inner = live[0], live[1:]
        n_pods = outer[1]
        n_inner = max(1, world // n_pods)
        w_in, m_in, b_in, ax_in = _ring_gather(inner, pair_bytes, topo)
        merge = (decode_sum(n_inner) if n_inner > 1 else 0.0) + compress2
        if strategy == "hierarchical":
            w_out, m_out, b_out, ax_out = _ring_gather([outer], pair_bytes,
                                                       topo)
            merge += decode_sum(n_pods) if n_pods > 1 else 0.0
        else:
            w_out, m_out, b_out, ax_out = _gtopk_rounds([outer], pair_bytes,
                                                        topo)
            r_out = _log2_exact(n_pods, "pod-axis size")
            merge += (decode_sum(2) + (r_out - 1) * round_merge
                      if r_out else 0.0)
        wire, msgs, moved = w_in + w_out, m_in + m_out, b_in + b_out
        per_axis = dict(ax_in)
        for ax, dt in ax_out.items():
            per_axis[ax] = per_axis.get(ax, 0.0) + dt
    else:
        raise ValueError(f"unknown strategy {strategy!r}")

    return WirePrediction(strategy, wire, merge, msgs, moved,
                          tuple(sorted(per_axis.items())))


def choose_strategy(layout: BucketLayout,
                    axes: Sequence[Tuple[str, int]],
                    topo: Topology,
                    codec_dtype=None) -> TunerDecision:
    """Pick the fastest valid strategy for this layout on this topology.

    Candidates are filtered by mesh validity, priced by
    :func:`predict_wire_time`, and sorted by ``(total_s, TIE_RANK)`` —
    by construction the selected strategy never predicts worse than any
    single strategy considered."""
    live = [(str(ax), int(n)) for ax, n in axes]
    cands = candidate_strategies([n for _, n in live])
    pair_bytes = layout.pair_bits(codec_dtype) / 8.0
    dense_bytes = float(layout.model_size) * layout.d_row_total * 4.0
    preds = [predict_wire_time(s, live, pair_bytes, dense_bytes, topo,
                               d_row=layout.d_row_total) for s in cands]
    preds.sort(key=lambda p: (p.total_s, TIE_RANK.get(p.strategy, 99)))
    return TunerDecision(preds[0].strategy, tuple(preds),
                         topology=topo.name)


# ---------------------------------------------------------------------------
# live measurement (the multihost CI validation leg)
# ---------------------------------------------------------------------------

def measure_wire_time(mesh, layout: BucketLayout, spec, strategy: str, *,
                      codec_dtype=None, reps: int = 5,
                      seed: int = 0) -> float:
    """Wall-clock seconds of one jitted wire stage (compress output ->
    aggregated mean) of ``strategy`` on the live mesh — what
    :func:`predict_wire_time` models.  Used by tools/launch_multihost.py
    to validate predicted vs measured time and ranking."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.dist.aggregate import (_gather_mean, _gtopk_reduce_bucket,
                                      bucket_compress, encode_bucket_topk)
    from repro.launch.mesh import data_axes_of
    from repro.launch.topo import _best_of

    axes = tuple(data_axes_of(mesh))
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    world = math.prod(sizes[a] for a in axes)
    M, D = layout.model_size, layout.d_row_total
    G = jax.random.normal(jax.random.PRNGKey(seed), (M, D), jnp.float32)
    values, indices = encode_bucket_topk(G, layout, codec_dtype)
    R2 = jnp.zeros((M, D), jnp.float32)

    def wire(values, indices, R2):
        if strategy == "gtopk":
            dense, _ = _gtopk_reduce_bucket(values, indices, axes, layout,
                                            codec_dtype)
            return dense / world
        if strategy == "allgather":
            return _gather_mean(values, indices, axes, world, D,
                                jnp.float32)
        outer, inner = axes[0], axes[1:]
        n_pods = sizes[outer]
        mean = _gather_mean(values, indices, inner, world // n_pods, D,
                            jnp.float32)
        v2, i2, _, _ = bucket_compress(
            mean, R2, layout, spec, jax.random.PRNGKey(seed),
            codec_dtype=codec_dtype, backend="reference", key_fold=1)
        if strategy == "hier_gtopk":
            dense2, _ = _gtopk_reduce_bucket(v2, i2, (outer,), layout,
                                             codec_dtype)
            return dense2 / n_pods
        return _gather_mean(v2, i2, outer, n_pods, D, jnp.float32)

    if strategy in ("hierarchical", "hier_gtopk") and len(axes) < 2:
        raise ValueError(f"{strategy} needs >= 2 data axes on this mesh")
    fn = jax.jit(jax.shard_map(
        wire, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
        axis_names=set(mesh.axis_names), check_vma=False))
    return _best_of(lambda: fn(values, indices, R2).block_until_ready(),
                    reps)


def measure_wire_pattern(mesh, pair_bytes: float, strategy: str, *,
                         reps: int = 7) -> float:
    """Wall-clock seconds of ``strategy``'s bare collective pattern on
    the live mesh — exactly the dispatches :func:`predict_wire_time`'s
    ``wire_s`` term prices (values + indices as separate messages, the
    modelled payload, no decode/merge compute), minus a jitted no-op
    baseline (call overhead is not wire time).  This is the multihost
    CI leg's measured side: on a host-device fabric the full wire stage
    of :func:`measure_wire_time` is dominated by XLA-CPU top-k compute
    that the alpha-beta terms deliberately do not model."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.dist.aggregate import gtopk_round_plan
    from repro.launch.mesh import data_axes_of
    from repro.launch.topo import _best_of

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes = tuple(data_axes_of(mesh))
    words = max(1, int(pair_bytes) // (2 * 4))   # two arrays = one pair
    v0 = jax.random.normal(jax.random.PRNGKey(0), (words,), jnp.float32)
    i0 = jnp.arange(words, dtype=jnp.int32)

    def rounds_over(v, i, ax_list):
        for ax in ax_list:
            if sizes[ax] <= 1:
                continue
            for _, mask, _ in gtopk_round_plan([sizes[ax]]):
                perm = [(j, j ^ mask) for j in range(sizes[ax])]
                v = jax.lax.ppermute(v, ax, perm)
                i = jax.lax.ppermute(i, ax, perm)
                v, i = jax.lax.optimization_barrier((v, i))
        return v, i

    def gather_over(v, i, ax_list):
        live = tuple(a for a in ax_list if sizes[a] > 1)
        if not live:
            return v, i
        va = jax.lax.all_gather(v, live)
        ia = jax.lax.all_gather(i, live)
        return va, ia

    def consume(*arrs):
        # pin the collectives with a barrier, then read only a fixed
        # 8-element window: summing the FULL gathered buffer would add a
        # W-scaled dense sweep (merge compute) to what must stay a pure
        # wire measurement, biased against the gather strategies
        arrs = jax.lax.optimization_barrier(tuple(arrs))
        return sum(a.ravel()[:8].sum().astype(jnp.float32) for a in arrs)

    def body(v, i):
        if strategy == "allgather":
            va, ia = gather_over(v, i, axes)
        elif strategy == "gtopk":
            va, ia = rounds_over(v, i, axes)
        elif strategy in ("hierarchical", "hier_gtopk"):
            if len(axes) < 2:
                raise ValueError(f"{strategy} needs >= 2 data axes")
            va, ia = gather_over(v, i, axes[1:])
            va, ia = jax.lax.optimization_barrier((va, ia))
            if strategy == "hier_gtopk":
                vo, io = rounds_over(v, i, axes[:1])
            else:
                vo, io = gather_over(v, i, axes[:1])
            return consume(va, ia, vo, io)
        else:
            raise ValueError(f"unknown strategy {strategy!r}")
        return consume(va, ia)

    def null(v, i):
        return consume(v * 1.0, i)

    def timed(f):
        fn = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
            axis_names=set(mesh.axis_names), check_vma=False))
        return _best_of(lambda: fn(v0, i0).block_until_ready(), reps)

    return max(timed(body) - timed(null), 1e-9)
