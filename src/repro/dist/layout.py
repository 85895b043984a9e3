"""Flat bucketed gradient layout — one wire message per step (DESIGN.md §10).

The paper's Eq.-2 cost model charges wire volume per *element*, but a
per-leaf aggregation loop pays per *leaf*: L gradient leaves mean L tiny
``(model_size, k_cap)`` collectives per step (L·log W ppermute rounds
for gTop-k) — latency-bound, exactly the per-tensor overhead Yoon & Oh
(arXiv:2209.08497) measure dominating TopK-SGD at scale.  This module is
the static geometry that collapses the loop:

* ``BucketLayout`` is computed ONCE at state-init from the param pytree.
  Every leaf's zero-padded ``(model_size, d_row)`` rows occupy a static
  column range ``[row_off, row_off + d_row)`` of one contiguous
  ``(model_size, d_row_total)`` gradient/residual bucket, and every
  leaf's fixed-capacity codec pair occupies a static column range
  ``[cap_off, cap_off + k_cap)`` of one ``(model_size, k_cap_total)``
  wire block.
* Selection stays per leaf segment (bit-identical to the per-leaf path:
  the same kernels run on the same row values with the same block
  configuration), but the *wire* becomes one concatenated codec pair
  whose indices are globalized by ``row_off`` — so each wire level is
  exactly ONE logical collective per step, independent of leaf count:

  =============  ==================  =====================
  strategy       per-leaf pipeline   bucketed pipeline
  =============  ==================  =====================
  allgather      L all-gathers       1 all-gather
  hierarchical   2·L all-gathers     2 all-gathers
  gtopk          L·log2(W) rounds    log2(W) rounds
  =============  ==================  =====================

  (a "collective" here is one codec-pair message; on the wire it is two
  array collectives, values + indices, of compile-time-constant size).

Residuals live in the flat bucket between steps (``TrainState["resid"]``
is ``(workers, model_size * d_row_total)``); ``checkpoint/npz.py`` loads
legacy per-leaf checkpoints through a migration shim built on
``pack_residual_arrays``.

The per-leaf RNG salt is a *stable hash of the leaf path* (not the
flatten index): adding a parameter to the tree must not reshuffle every
other leaf's randk/dgck sampling, and the per-leaf and bucketed paths
must key identically for bit-equality.
"""
from __future__ import annotations

import hashlib
import math
import warnings
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import adaptk
from repro.core.compression import STRATEGIES, CompressionConfig
from repro.core.compressors import CompressorSpec

# ---------------------------------------------------------------------------
# wire model (single source: per-leaf metrics, layout metrics, benchmarks)
# ---------------------------------------------------------------------------


def _log2_exact(n: int, what: str = "world size") -> int:
    """log2 of a power of two; raises for anything else (the XOR pairing
    of the recursive-doubling tree needs exact halving at every round)."""
    if n < 1 or n & (n - 1):
        raise ValueError(
            f"gtopk strategy needs a power-of-two {what}, got {n}; "
            "use strategy='allgather' on ragged meshes")
    return n.bit_length() - 1


def resolve_strategy(strategy: str, hierarchical: bool = False) -> str:
    """Normalize the legacy ``hierarchical=True`` flag into the strategy
    vocabulary (single source of the precedence rule for every layer and
    CLI): it promotes the default ``"allgather"`` only — an explicitly
    chosen strategy always wins.  Raises on unknown strategies.

    ``hierarchical=True`` is deprecated — THE shim boundary for the
    retired boolean flag; pass ``strategy="hierarchical"`` (or a
    ``CompressionConfig``) instead."""
    if hierarchical:
        warnings.warn(
            "hierarchical=True is deprecated; pass "
            "strategy='hierarchical' (or CompressionConfig("
            "strategy='hierarchical')) instead",
            DeprecationWarning, stacklevel=2)
        if strategy == "allgather":
            return "hierarchical"
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown strategy {strategy!r}; have {STRATEGIES}")
    return strategy


def strategy_wire_pairs(strategy: str, world: int, n_pods: int = 1) -> int:
    """Number of ``(k_cap,)`` codec pairs a worker moves per wire row.

    The compile-time wire-volume model behind the ``comm_bits_sparse`` /
    ``wire_bytes`` metrics and ``benchmarks/table2_scaling.py``:

      allgather     ``W``               (every worker's pair lands on
                                        every worker)
      hierarchical  ``W_inner + P_pod`` (pod gather + pod-mean gather)
      gtopk         ``log2(W)``         (one pair sent per halving round)
      hier_gtopk    ``W_inner + log2(P_pod)``
                                        (pod gather + recursive-doubling
                                        rounds across pods)
    """
    if strategy == "gtopk":
        return _log2_exact(world)
    if strategy == "hierarchical":
        return max(1, world // n_pods) + n_pods
    if strategy == "hier_gtopk":
        return max(1, world // n_pods) + _log2_exact(n_pods,
                                                     "pod-axis size")
    if strategy == "allgather":
        return world
    raise ValueError(f"unknown strategy {strategy!r}; have {STRATEGIES}")


def collective_count(strategy: str, world: int, n_pods: int = 1,
                     leaves: int = 1) -> int:
    """Codec-pair collectives dispatched per step.

    ``leaves=1`` is the bucketed pipeline (the whole point: one wire
    message per level); ``leaves=L`` models the per-leaf loop.  gTop-k
    counts its ppermute rounds, the gather strategies their all-gathers
    (one per level); the hybrid is one inner gather plus ``log2(P)``
    outer ppermute rounds.
    """
    if strategy == "gtopk":
        return leaves * _log2_exact(world)
    if strategy == "hierarchical":
        return leaves * 2
    if strategy == "hier_gtopk":
        return leaves * (1 + _log2_exact(n_pods, "pod-axis size"))
    if strategy == "allgather":
        return leaves
    raise ValueError(f"unknown strategy {strategy!r}; have {STRATEGIES}")


# ---------------------------------------------------------------------------
# per-leaf geometry (shared with the per-leaf path in dist/aggregate.py)
# ---------------------------------------------------------------------------


def flat_dims(size: int, model_size: int) -> Tuple[int, int]:
    """(padded flat length, per-model-shard row length) for a leaf."""
    d_pad = -(-size // model_size) * model_size
    return d_pad, d_pad // model_size


def row_budget(k: int, model_size: int, d_row: int) -> int:
    """Per-row share of a leaf-level element budget: ``ceil(k /
    model_size)`` clamped to ``[1, d_row]`` — the ONE rounding rule that
    sizes both the selection budget and (through ``spec.k_cap``) the
    static codec capacity, shared by the fixed and adaptive plans and by
    ``build_layout``."""
    return min(d_row, max(1, -(-k // model_size)))


def leaf_plan(size: int, model_size: int, ratio: float,
              spec: CompressorSpec) -> Tuple[int, int, int, int]:
    """(d_pad, d_row, k_row, k_cap_row) for one leaf.

    ``k = max(1, ceil(ratio * size))`` global budget, split evenly over
    the model shards; the row capacity is the compressor's own
    over-selection cap (e.g. 4k/3 for Gaussian-k).
    """
    d_pad, d_row = flat_dims(size, model_size)
    k = max(1, math.ceil(ratio * size))
    k_row = row_budget(k, model_size, d_row)
    k_cap = min(d_row, spec.k_cap(k_row, d_row))
    return d_pad, d_row, k_row, k_cap


def leaf_plan_adaptive(size: int, model_size: int, ratio: float,
                       spec: CompressorSpec, policy: adaptk.DensityPolicy):
    """(d_pad, d_row, k_lo, k_hi, k_cap_row) for one leaf under an
    adaptive density policy.

    ``[k_lo, k_hi]`` are the leaf-level integer clamps the allocator
    respects; every static shape — the codec row capacity ``k_cap_row``
    and, downstream, staging widths and wire volume — derives from the
    *ceiling* ``k_hi``, so the per-step traced ``k`` can move anywhere
    inside the clamp without touching a single buffer shape.
    """
    d_pad, d_row = flat_dims(size, model_size)
    k_lo, k_hi = adaptk.leaf_bounds(size, ratio, policy)
    k_cap = min(d_row, spec.k_cap(row_budget(k_hi, model_size, d_row),
                                  d_row))
    return d_pad, d_row, k_lo, k_hi, k_cap


# ---------------------------------------------------------------------------
# stable per-leaf RNG salt
# ---------------------------------------------------------------------------


def leaf_path_name(path) -> str:
    """Canonical '/'-joined name of a pytree leaf path — the SAME join
    convention as ``checkpoint/npz.py`` flat keys, so checkpoint keys and
    layout segments address leaves identically."""
    return "/".join(
        str(getattr(e, "key", getattr(e, "idx", e))) for e in path)


def leaf_key_salt(name: str) -> int:
    """Stable 31-bit RNG salt of a leaf-path name.

    ``jax.random.fold_in(key, leaf_key_salt(name))`` replaces the old
    ``fold_in(key, flatten_index)`` keying: the salt depends only on the
    leaf's *path*, so inserting or removing a parameter elsewhere in the
    tree leaves every other leaf's randk/dgck sampling untouched.
    blake2s (not ``hash()``) — deterministic across processes and runs.
    """
    digest = hashlib.blake2s(name.encode(), digest_size=4).digest()
    return int.from_bytes(digest, "big") & 0x7FFFFFFF


# ---------------------------------------------------------------------------
# the layout
# ---------------------------------------------------------------------------


class LeafSegment(NamedTuple):
    """Static geometry of one gradient leaf inside the bucket."""
    name: str          # stable '/'-joined tree path (checkpoint key)
    shape: Tuple[int, ...]
    dtype: str         # leaf dtype name (agg means are cast back to it)
    size: int          # true (unpadded) element count
    d_pad: int         # padded flat length (multiple of model_size)
    d_row: int         # per-model-shard row length
    row_off: int       # column offset into the (model_size, d_row_total) bucket
    k_row: int         # fixed-k per-row budget (ceiling-derived if adaptive)
    k_cap: int         # per-row codec capacity
    cap_off: int       # column offset into the (model_size, k_cap_total) wire block
    k_lo: int          # adaptive per-leaf floor (== k budget when fixed)
    k_hi: int          # adaptive per-leaf ceiling (== k budget when fixed)
    salt: int          # stable RNG salt (leaf_key_salt of name)


class BucketLayout(NamedTuple):
    """Static bucket geometry for one (params, model_size, ratio, spec,
    density_policy) configuration — compute once, close over in the
    jitted step.  All fields are Python ints/tuples: hashable,
    trace-free."""
    segments: Tuple[LeafSegment, ...]
    model_size: int
    ratio: float
    spec_name: str
    adaptive: bool
    d_row_total: int   # bucket columns: sum of d_row over segments
    k_cap_total: int   # wire columns: sum of k_cap over segments

    # -- derived accounting ------------------------------------------------
    @property
    def d_total(self) -> int:
        """True (unpadded) parameter count across segments."""
        return sum(s.size for s in self.segments)

    @property
    def flat_size(self) -> int:
        """Length of the flat residual buffer: model_size * d_row_total."""
        return self.model_size * self.d_row_total

    def pair_bits(self, codec_dtype=None) -> int:
        """Wire bits of ONE bucketed codec pair (all leaves, all rows)."""
        val_bits = jnp.dtype(codec_dtype).itemsize * 8 if codec_dtype else 32
        return self.model_size * self.k_cap_total * (val_bits + 32)

    def comm_bits_sparse(self, strategy: str, world: int, n_pods: int = 1,
                         codec_dtype=None) -> float:
        """Per-worker sparse wire volume per step — identical to the sum
        the per-leaf loop accumulates (Σ_leaf levels·M·k_cap·pair_bits ==
        levels·M·K_cap_total·pair_bits)."""
        levels = strategy_wire_pairs(strategy, world, n_pods)
        return float(levels * self.pair_bits(codec_dtype))

    def comm_bits_dense(self) -> float:
        """Dense ring-all-reduce baseline (2·d per worker) in bits."""
        return float(sum(
            2 * s.size * jnp.dtype(s.dtype).itemsize * 8
            for s in self.segments))

    def collectives(self, strategy: str, world: int, n_pods: int = 1) -> int:
        """Codec-pair collectives this layout dispatches per step (1 per
        wire level; log2(W) rounds for gTop-k) — leaf-count independent."""
        return collective_count(strategy, world, n_pods, leaves=1)


def build_layout(params, model_size: int, ratio,
                 spec: Optional[CompressorSpec] = None,
                 density_policy: Optional[adaptk.DensityPolicy] = None,
                 ) -> BucketLayout:
    """Compute the static bucket geometry from a param/grad pytree.

    The third argument is either the density ``ratio`` (with ``spec``
    and optionally ``density_policy`` alongside) or a
    :class:`~repro.core.compression.CompressionConfig`, which supplies
    all three — the config-first spelling shared with ``make_train_step``
    and the serve publisher.

    Segment order is the tree flatten order (matching
    ``jax.tree.flatten`` and the adaptk controller's signal vector);
    offsets are exclusive prefix sums of ``d_row`` / ``k_cap``.  Raises
    on a salt collision (two leaf paths hashing to the same 31-bit salt
    would silently correlate their sampling — astronomically unlikely,
    but fail loudly rather than degrade).
    """
    if isinstance(ratio, CompressionConfig):
        if spec is not None or density_policy is not None:
            raise TypeError("build_layout: pass EITHER a CompressionConfig "
                            "OR (ratio, spec, density_policy), not both")
        cfg = ratio
        if cfg.dense:
            raise ValueError("cannot build a BucketLayout for Dense-SGD "
                             "(compressor='none')")
        ratio, spec, density_policy = cfg.ratio, cfg.spec, cfg.density_policy
    elif spec is None:
        raise TypeError("build_layout needs a CompressorSpec when called "
                        "with a plain ratio")
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    if not leaves:
        raise ValueError("cannot build a BucketLayout over an empty pytree")
    segments = []
    row_off = cap_off = 0
    seen_salts = {}
    for path, leaf in leaves:
        name = leaf_path_name(path)
        size = int(leaf.size)
        if density_policy is not None:
            d_pad, d_row, k_lo, k_hi, k_cap = leaf_plan_adaptive(
                size, model_size, ratio, spec, density_policy)
            k_row = row_budget(k_hi, model_size, d_row)
        else:
            d_pad, d_row, k_row, k_cap = leaf_plan(size, model_size, ratio,
                                                   spec)
            k_lo = k_hi = max(1, math.ceil(ratio * size))
        salt = leaf_key_salt(name)
        if salt in seen_salts:
            raise ValueError(
                f"leaf-path salt collision: {name!r} and "
                f"{seen_salts[salt]!r} both hash to {salt}")
        seen_salts[salt] = name
        segments.append(LeafSegment(
            name=name, shape=tuple(leaf.shape),
            dtype=jnp.dtype(leaf.dtype).name, size=size, d_pad=d_pad,
            d_row=d_row, row_off=row_off, k_row=k_row, k_cap=k_cap,
            cap_off=cap_off, k_lo=int(k_lo), k_hi=int(k_hi), salt=salt))
        row_off += d_row
        cap_off += k_cap
    return BucketLayout(segments=tuple(segments), model_size=model_size,
                        ratio=float(ratio), spec_name=spec.name,
                        adaptive=density_policy is not None,
                        d_row_total=row_off, k_cap_total=cap_off)


def rebudget_layout(layout: BucketLayout, ratio: float,
                    spec: CompressorSpec) -> BucketLayout:
    """The same bucket re-budgeted at a different (ratio, spec) — the
    delta-layout reuse behind the serve publisher (DESIGN.md §13).

    Row geometry (``d_row``, ``row_off``, names, salts, segment order)
    depends only on leaf sizes and ``model_size``, so it is carried over
    verbatim: a residual or params bucket packed under ``layout`` is
    byte-compatible with the re-budgeted one.  Only the codec capacities
    (``k_row``, ``k_cap``, ``cap_off``) are recomputed, fixed-k — the
    publisher never runs adaptive density."""
    if isinstance(ratio, CompressionConfig):
        raise TypeError("rebudget_layout takes a plain ratio + spec "
                        "(build_layout accepts the config spelling)")
    segments, cap_off = [], 0
    for s in layout.segments:
        k = max(1, math.ceil(ratio * s.size))
        k_row = row_budget(k, layout.model_size, s.d_row)
        k_cap = min(s.d_row, spec.k_cap(k_row, s.d_row))
        segments.append(s._replace(k_row=k_row, k_cap=k_cap,
                                   cap_off=cap_off, k_lo=k, k_hi=k))
        cap_off += k_cap
    return BucketLayout(segments=tuple(segments),
                        model_size=layout.model_size, ratio=float(ratio),
                        spec_name=spec.name, adaptive=False,
                        d_row_total=layout.d_row_total, k_cap_total=cap_off)


# ---------------------------------------------------------------------------
# pack / unpack
# ---------------------------------------------------------------------------


@jax.named_scope("bucket.pack")
def pack_grads(layout: BucketLayout, grads, dtype) -> jax.Array:
    """Pack a gradient pytree into the ``(model_size, d_row_total)``
    bucket: each leaf is flattened, zero-padded to ``d_pad``, cast to
    ``dtype`` (the residual accumulation dtype — the same cast the
    per-leaf path applies at pad time) and reshaped to its row block.
    One concatenate — no per-leaf device dispatch."""
    leaves = jax.tree.leaves(grads)
    if len(leaves) != len(layout.segments):
        raise ValueError(f"tree has {len(leaves)} leaves, layout has "
                         f"{len(layout.segments)} segments")
    blocks = []
    for seg, g in zip(layout.segments, leaves):
        if int(g.size) != seg.size:
            raise ValueError(f"leaf {seg.name!r}: size {g.size} != layout "
                             f"size {seg.size}")
        flat = jnp.pad(g.reshape(-1), (0, seg.d_pad - seg.size)).astype(dtype)
        blocks.append(flat.reshape(layout.model_size, seg.d_row))
    return jnp.concatenate(blocks, axis=1)


@jax.named_scope("bucket.unpack")
def unpack_tree(layout: BucketLayout, bucket: jax.Array, treedef=None,
                like=None):
    """Slice the ``(model_size, d_row_total)`` bucket back into the leaf
    tree: per segment, the row block is flattened, truncated to the true
    size and cast back to the leaf dtype.  ``like`` (a matching pytree)
    supplies the treedef AND the target dtypes — the *runtime* leaf
    dtype wins over the dtype frozen into the layout at build time, so a
    caller feeding e.g. f32 gradients through a layout built from bf16
    params gets f32 back, exactly like the per-leaf path's
    ``.astype(g.dtype)``.  With only ``treedef`` the layout dtypes
    apply."""
    if treedef is None:
        treedef = jax.tree.structure(like)
    like_leaves = (jax.tree.leaves(like) if like is not None
                   else [None] * len(layout.segments))
    leaves = []
    for seg, ll in zip(layout.segments, like_leaves):
        block = bucket[:, seg.row_off:seg.row_off + seg.d_row]
        dtype = seg.dtype if ll is None else ll.dtype
        leaves.append(block.reshape(-1)[:seg.size].reshape(seg.shape)
                      .astype(dtype))
    return jax.tree.unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# chunked schedule geometry (DESIGN.md §11)
# ---------------------------------------------------------------------------


class ChunkGroup(NamedTuple):
    """One contiguous run of leaf segments of the chunked wire schedule.

    ``[seg_lo, seg_hi)`` indexes into ``BucketLayout.segments``; the
    offsets/extents are the group's static column window of the global
    ``(model_size, d_row_total)`` bucket and ``(model_size, k_cap_total)``
    wire block."""
    index: int
    seg_lo: int
    seg_hi: int
    row_off: int       # first bucket column of the group
    d_row: int         # bucket columns the group spans
    cap_off: int       # first wire-block column of the group
    k_cap: int         # wire-block columns the group spans


class ChunkPlan(NamedTuple):
    """Static partition of a ``BucketLayout`` into N contiguous,
    leaf-aligned chunk groups (DESIGN.md §11).

    Chunk boundaries never split a leaf segment: selection, RNG salting
    and the codec index space are all per-segment, so a leaf-aligned cut
    leaves every segment's computation byte-identical to the unchunked
    schedule — only the wire dispatch granularity changes.  ``n_chunks``
    is therefore clamped to the segment count (``requested`` records the
    caller's ask)."""
    n_chunks: int
    requested: int
    groups: Tuple[ChunkGroup, ...]

    def collectives(self, strategy: str, world: int, n_pods: int = 1) -> int:
        """Codec-pair collectives per step under this plan: the per-level
        count of the unchunked bucket, once per chunk."""
        return self.n_chunks * collective_count(strategy, world, n_pods,
                                                leaves=1)


def build_chunk_plan(layout: BucketLayout, n_chunks: int) -> ChunkPlan:
    """Partition the layout's segments into ``n_chunks`` contiguous
    groups, balanced by cumulative bucket width ``d_row``.

    Deterministic greedy cut: boundary j lands on the first segment whose
    cumulative width reaches ``j/n`` of the total (while leaving enough
    segments for the remaining groups) — same inputs, same plan, on every
    process.  ``n_chunks`` is clamped to the segment count (a chunk
    cannot be narrower than one leaf); ``n_chunks=1`` is the unchunked
    schedule."""
    if n_chunks < 1:
        raise ValueError(f"n_chunks must be >= 1, got {n_chunks}")
    segs = layout.segments
    n = min(int(n_chunks), len(segs))
    cums = []
    tot = 0
    for s in segs:
        tot += s.d_row
        cums.append(tot)
    bounds = [0]
    for j in range(1, n):
        target = j * tot / n
        lo, hi = bounds[-1] + 1, len(segs) - (n - j)
        cut = hi
        for i in range(lo, hi + 1):
            if cums[i - 1] >= target:
                cut = i
                break
        bounds.append(cut)
    bounds.append(len(segs))
    groups = []
    for c in range(n):
        first, last = segs[bounds[c]], segs[bounds[c + 1] - 1]
        groups.append(ChunkGroup(
            index=c, seg_lo=bounds[c], seg_hi=bounds[c + 1],
            row_off=first.row_off,
            d_row=last.row_off + last.d_row - first.row_off,
            cap_off=first.cap_off,
            k_cap=last.cap_off + last.k_cap - first.cap_off))
    return ChunkPlan(n_chunks=n, requested=int(n_chunks),
                     groups=tuple(groups))


def validate_chunk_plan(layout: BucketLayout, plan: ChunkPlan) -> None:
    """Fail loudly if ``plan`` does not tile ``layout`` exactly — a plan
    built from a different layout silently corrupts the residual
    windows, so this runs at every chunked-aggregation entry."""
    if not plan.groups or plan.n_chunks != len(plan.groups):
        raise ValueError(f"malformed ChunkPlan: n_chunks={plan.n_chunks}, "
                         f"{len(plan.groups)} groups")
    seg, row, cap = 0, 0, 0
    for g in plan.groups:
        if (g.seg_lo, g.row_off, g.cap_off) != (seg, row, cap):
            raise ValueError(
                f"chunk {g.index} starts at (seg={g.seg_lo}, "
                f"row={g.row_off}, cap={g.cap_off}), expected "
                f"({seg}, {row}, {cap}) — plan does not tile this layout")
        if g.seg_hi <= g.seg_lo:
            raise ValueError(f"chunk {g.index} is empty")
        seg, row, cap = g.seg_hi, g.row_off + g.d_row, g.cap_off + g.k_cap
    if (seg, row, cap) != (len(layout.segments), layout.d_row_total,
                           layout.k_cap_total):
        raise ValueError(
            f"plan covers (seg={seg}, row={row}, cap={cap}) but layout "
            f"has ({len(layout.segments)}, {layout.d_row_total}, "
            f"{layout.k_cap_total}) — plan built from a different layout?")


def chunk_view(layout: BucketLayout, group: ChunkGroup) -> BucketLayout:
    """The group's window of the layout as a standalone ``BucketLayout``.

    Segments keep their name, salt, static plan and order; only
    ``row_off``/``cap_off`` are rebased to the group's window.  Because
    every bucketed primitive (``bucket_compress``, ``encode_bucket_topk``,
    ``_gather_mean`` decode, the gTop-k merge) is per-segment over
    ``[row_off, row_off + d_row)`` and the codec sentinel is offset-
    independent, running them on the sub-layout over the window slice is
    bit-identical to the same columns of the full-bucket run — which is
    what makes the chunked schedule a pure re-dispatch."""
    segs = tuple(
        s._replace(row_off=s.row_off - group.row_off,
                   cap_off=s.cap_off - group.cap_off)
        for s in layout.segments[group.seg_lo:group.seg_hi])
    return BucketLayout(segments=segs, model_size=layout.model_size,
                        ratio=layout.ratio, spec_name=layout.spec_name,
                        adaptive=layout.adaptive,
                        d_row_total=group.d_row, k_cap_total=group.k_cap)


def init_flat_residual(layout: BucketLayout, dtype=jnp.float32) -> jax.Array:
    """Zero flat residual bucket, ``(model_size * d_row_total,)`` —
    the flat-buffer replacement for the per-leaf residual tree."""
    return jnp.zeros((layout.flat_size,), dtype)


def pack_residual_arrays(layout: BucketLayout, arrays: Sequence):
    """Pack per-leaf flat-padded residual arrays into the flat bucket.

    ``arrays`` follow segment order, each shaped ``(..., d_pad)`` (any
    leading dims — e.g. the per-worker axis of checkpointed residuals).
    This is the checkpoint migration primitive: bit-wise, the packed
    buffer's ``[..., model_size, row_off:row_off+d_row]`` view equals the
    legacy leaf's ``(..., model_size, d_row)`` reshape.  Raises loudly on
    count/shape mismatches (truncated or invalid legacy layouts).
    """
    import numpy as np
    if len(arrays) != len(layout.segments):
        raise ValueError(f"got {len(arrays)} residual arrays for "
                         f"{len(layout.segments)} layout segments")
    blocks, lead = [], None
    for seg, a in zip(layout.segments, arrays):
        a = np.asarray(a)
        if a.ndim < 1 or a.shape[-1] != seg.d_pad:
            raise ValueError(
                f"segment {seg.name!r}: residual shape {a.shape} does not "
                f"end in d_pad={seg.d_pad} (truncated or mismatched "
                "legacy layout)")
        if lead is None:
            lead = a.shape[:-1]
        elif a.shape[:-1] != lead:
            raise ValueError(
                f"segment {seg.name!r}: leading dims {a.shape[:-1]} != "
                f"{lead} of earlier segments")
        blocks.append(a.reshape(lead + (layout.model_size, seg.d_row)))
    packed = np.concatenate(blocks, axis=-1)
    return packed.reshape(lead + (layout.flat_size,))


def unpack_residual_arrays(layout: BucketLayout, flat):
    """Inverse of :func:`pack_residual_arrays`: the flat bucket back into
    per-leaf ``(..., d_pad)`` arrays in segment order."""
    import numpy as np
    flat = np.asarray(flat)
    if flat.shape[-1] != layout.flat_size:
        raise ValueError(f"flat residual has trailing dim {flat.shape[-1]}, "
                         f"layout expects {layout.flat_size}")
    lead = flat.shape[:-1]
    rows = flat.reshape(lead + (layout.model_size, layout.d_row_total))
    out = []
    for seg in layout.segments:
        block = rows[..., seg.row_off:seg.row_off + seg.d_row]
        out.append(block.reshape(lead + (seg.d_pad,)))
    return out
