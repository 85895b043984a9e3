"""Compressed gradient aggregation — paper Eq. (2) on a device mesh.

Runs inside the train step's shard_map region: manual over the data axes
(one program instance per data-parallel worker), auto/GSPMD over
``model``.  Per gradient leaf and per worker (DESIGN.md §3-§4):

  1. flatten + zero-pad to ``d_pad`` (a multiple of ``model_size``) and
     fold in the worker's error-feedback residual: ``u = e + g``,
  2. reshape to ``(model_size, d_row)`` rows — one row per model shard —
     and run the compressor row-wise with a per-row budget
     ``k_row = ceil(k / model_size)``, giving a fixed-capacity sparse
     ``(values, indices)`` pair per row,
  3. all-gather the pairs over the data axes (wire volume is the
     compile-time constant ``W * model_size * k_cap * (bits_v + 32)``),
  4. sentinel-aware decode of every worker's pair, sum, divide by the
     world size — the Eq. (2) average,
  5. new residual ``e' = u - decode(own pair)``: exactly the mass the
     wire did not carry (including any ``codec_dtype`` down-cast error).

Step 3-4 is the ``strategy`` choice (DESIGN.md §3, §7):

``"allgather"``     flat sparse all-gather over all data axes —
                    ``O(W)`` codec pairs per worker.
``"hierarchical"``  two-level pod -> global reduction: gather/average
                    within the pod over the inner data axes, then
                    compress the pod-mean again against the second
                    residual ``resid2`` and gather/average over the
                    ``pod`` axis — ``O(W_inner + n_pods)`` pairs at the
                    price of a second (also error-fed) compression.
``"gtopk"``         gTop-k recursive doubling (Shi et al.,
                    arXiv:1901.04359): ``log2(W)`` ppermute rounds of
                    pairwise codec merges (decode both ``(k_cap,)``
                    pairs, scatter-add, re-select top-``k_cap``,
                    re-encode) — ``O(log W)`` pairs per worker, one
                    ``(k_cap,)`` pair per round.  Mass dropped by a
                    merge re-selection is credited back to the merging
                    workers' residuals (divided by the replica count of
                    that merge) so Eq. (2) conservation holds globally.
``"hier_gtopk"``    the two-level hybrid (DESIGN.md §14): pod-level
                    gather + second error-fed compression exactly as
                    ``"hierarchical"``, then gTop-k recursive doubling
                    across the ``pod`` axis instead of the pod-mean
                    gather — ``O(W_inner + log2 n_pods)`` pairs.  Outer
                    merge drops are credited into ``resid2`` UN-divided
                    by ``n_pods``: ``resid2`` is pod-replicated, so one
                    representative worker per pod recovers the dropped
                    mass exactly once (the ``hierarchical`` convention).

TWO dispatch granularities implement the same semantics (DESIGN.md §10):

``aggregate_compressed``  the per-leaf loop — one collective chain per
                          gradient leaf.  Reference/teaching path and
                          bit-equality oracle.
``aggregate_bucketed``    the flat bucketed pipeline over a static
                          ``dist/layout.BucketLayout``: selection still
                          runs per leaf segment (bit-identical), but the
                          wire is ONE concatenated codec block per level
                          per step — 1 all-gather (allgather), 2
                          (hierarchical), log2(W) merged ppermute rounds
                          total (gtopk), 1 + log2(n_pods) (hier_gtopk),
                          independent of leaf count.

``momentum_correction > 0`` enables the DGC §3.1 client-side momentum
blend: ``v = mu*v + g; u = e + v``; coordinates that make it onto the
wire are zeroed in ``v`` (``resid2`` doubles as the ``v`` state — it is
mutually exclusive with ``hierarchical``).

``density_policy`` switches step 2 to the adaptive layer-wise density
path (``core/adaptk``, DESIGN.md §9): per-leaf pass-A moments →
pmean'd allocation signal → budget-exact redistribution of the global
``K_total(step)`` into per-leaf *traced* budgets, with every static
capacity (codec ``k_cap``, staging, wire volume) derived from the
policy's ceiling clamp.

Per-leaf RNG keys fold in a *stable hash of the leaf path*
(``layout.leaf_key_salt``), not the flatten index — adding a parameter
to the model must not reshuffle every other leaf's randk/dgck sampling,
and the two dispatch granularities must key identically.
"""
from __future__ import annotations

import warnings
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import adaptk, codec
from repro.core.compression import CompressionConfig
from repro.core.compressors import CompressorSpec
from repro.core.error_feedback import resolve_backend
# geometry + wire model live in dist/layout.py (single source for both
# dispatch granularities); re-exported here for API compatibility
from repro.dist.layout import (STRATEGIES, BucketLayout,  # noqa: F401
                               ChunkPlan, _log2_exact, chunk_view,
                               collective_count, flat_dims, leaf_key_salt,
                               leaf_path_name, leaf_plan, leaf_plan_adaptive,
                               pack_grads, resolve_strategy,
                               strategy_wire_pairs, unpack_tree,
                               validate_chunk_plan)
from repro.kernels.ef_fused.segmented import (rows_compress_ef, rows_pass_a,
                                              segmented_compress_ef,
                                              segmented_pass_a)

# ---------------------------------------------------------------------------
# result type + config shims (shared by all aggregation entry points)
# ---------------------------------------------------------------------------


class AggregateResult(NamedTuple):
    """What every aggregation entry point returns (replaces the legacy
    positional 5-tuple — same field order, so old unpacking code keeps
    working through one release while new code reads fields by name).

    ``agg``          the Eq.-2 averaged gradient, leaf tree shape/dtype.
    ``resid``        updated error-feedback residual (per-leaf tree or
                     flat bucket, matching the input).
    ``resid2``       updated second-level residual / DGC velocity state
                     (``None`` when neither is in play).
    ``adapt_state``  updated adaptk controller state (``None`` unless
                     adaptive density with a stateful controller).
    ``metrics``      replicated scalar metrics dict.
    """
    agg: Any
    resid: Any
    resid2: Any
    adapt_state: Any
    metrics: dict


_LEGACY_KEYS = ("strategy", "hierarchical", "codec_dtype",
                "momentum_correction", "backend", "density_policy")


def _config_from_legacy(fn: str, spec: CompressorSpec, ratio: float,
                        legacy: dict) -> CompressionConfig:
    """Build a :class:`CompressionConfig` from the deprecated loose-kwarg
    spelling (CompressorSpec positional + strategy/backend/... kwargs),
    warning loudly.  ``hierarchical=True`` routes through
    ``resolve_strategy`` — the one shim boundary for the retired flag."""
    warnings.warn(
        f"{fn}: passing a CompressorSpec with loose kwargs is deprecated; "
        "pass a core.compression.CompressionConfig instead",
        DeprecationWarning, stacklevel=3)
    strategy = resolve_strategy(legacy.pop("strategy", "allgather"),
                                legacy.pop("hierarchical", False))
    cfg = CompressionConfig(
        compressor=spec.name, ratio=float(ratio), strategy=strategy,
        codec_dtype=legacy.pop("codec_dtype", None),
        momentum_correction=float(legacy.pop("momentum_correction", 0.0)),
        backend=legacy.pop("backend", "auto"),
        density_policy=legacy.pop("density_policy", None))
    if legacy:
        raise TypeError(f"{fn}: unexpected kwargs {sorted(legacy)}")
    return cfg


def _require_config(fn: str, config, legacy: dict) -> CompressionConfig:
    """Config-first path: a real config and NO loose legacy kwargs."""
    if not isinstance(config, CompressionConfig):
        raise TypeError(
            f"{fn}: expected a CompressionConfig (or a legacy "
            f"CompressorSpec), got {type(config).__name__}")
    if legacy:
        raise TypeError(
            f"{fn}: legacy kwargs {sorted(legacy)} cannot be combined with "
            "a CompressionConfig — fold them in via config.replace(...)")
    if config.dense:
        raise ValueError(f"{fn}: compressor='none' is Dense-SGD; call "
                         "aggregate_dense instead")
    return config


# ---------------------------------------------------------------------------
# residual layout
# ---------------------------------------------------------------------------


def init_residuals(params, model_size: int, dtype=jnp.float32):
    """Zero error-feedback residuals, one flat-padded vector per leaf.

    Each leaf is ``(d_pad,)`` with ``d_pad = ceil(size/model_size) *
    model_size`` so the vector reshapes evenly into per-model-shard rows.
    The caller stacks a leading worker axis (see train/state.py).  The
    bucketed pipeline stores the same values in ONE flat buffer instead
    (``layout.init_flat_residual``).
    """
    def zero(p):
        d_pad, _ = flat_dims(p.size, model_size)
        return jnp.zeros((d_pad,), dtype)

    return jax.tree.map(zero, params)


# ---------------------------------------------------------------------------
# worker-local compression (pure: unit-testable without a mesh)
# ---------------------------------------------------------------------------


def _select_rows(spec: CompressorSpec, u_rows: jax.Array, k_row: int, key):
    if spec.needs_key:
        keys = jax.random.split(key, u_rows.shape[0])
        return jax.vmap(lambda r, kk: spec.select(r, k_row, kk))(u_rows, keys)
    return jax.vmap(lambda r: spec.select(r, k_row, None))(u_rows)


def _decode_rows(values: jax.Array, indices: jax.Array, d_row: int,
                 dtype) -> jax.Array:
    return jax.vmap(
        lambda v, i: codec.decode(v.astype(dtype), i, d_row))(values, indices)


@jax.named_scope("ef.residual")
def _wire_cast_fixup(values, indices, new_e_rows, codec_dtype):
    """Down-cast wire values and fold the cast error into the residual
    with a k-sized scatter-add (``e' += decode(values − cast(values))``)
    — bit-equal to the reference's dense ``u − decode(cast(values))``.
    Shared by the per-leaf and bucketed fused paths."""
    if codec_dtype is None:
        return values, indices, new_e_rows
    wire = values.astype(codec_dtype)
    diff = values - wire.astype(values.dtype)
    new_e_rows = jax.vmap(codec.decode_add)(new_e_rows, diff, indices)
    return wire, indices, new_e_rows


def _compress_rows_fused(g_rows: jax.Array, e_rows: jax.Array,
                         spec: CompressorSpec, k_row, k_cap: int,
                         codec_dtype=None, row_stats=None):
    """Fused EF compression of ``(model_size, d_row)`` rows (DESIGN.md §8)
    — ``kernels/ef_fused.rows_compress_ef`` plus the wire-dtype fixup."""
    values, indices, new_e_rows = rows_compress_ef(
        g_rows, e_rows, spec.name, k_row, k_cap=k_cap, row_stats=row_stats)
    values, indices, new_e_rows = _wire_cast_fixup(values, indices,
                                                   new_e_rows, codec_dtype)
    return values, indices, new_e_rows


@jax.named_scope("ef.residual")
def _compress_rows(g_rows: jax.Array, e_rows: jax.Array,
                   spec: CompressorSpec, k_row: int, k_cap: int, key, *,
                   codec_dtype=None, momentum: float = 0.0, v_rows=None,
                   backend: str = "auto"):
    """Row-level fixed-k EF compression of one ``(model_size, d_row)``
    block — the single code path behind both :func:`compress_worker`
    (per-leaf) and :func:`bucket_compress` (bucketed segment), which is
    what makes the two dispatch granularities bit-identical.

    Returns ``(values, indices, new_e_rows, new_v_rows)`` (``new_v_rows``
    is ``None`` unless ``momentum > 0``).
    """
    if momentum == 0.0 and resolve_backend(backend, spec):
        values, indices, new_e_rows = _compress_rows_fused(
            g_rows, e_rows, spec, k_row, k_cap, codec_dtype)
        return values, indices, new_e_rows, None
    if momentum > 0.0:
        v_rows = momentum * v_rows + g_rows
        u_rows = e_rows + v_rows
    else:
        u_rows = e_rows + g_rows
    d_row = u_rows.shape[1]
    values, indices = _select_rows(spec, u_rows, k_row, key)
    if codec_dtype is not None:
        values = values.astype(codec_dtype)
    decoded = _decode_rows(values, indices, d_row, u_rows.dtype)
    new_e_rows = u_rows - decoded
    new_v_rows = None
    if momentum > 0.0:
        # wire-exchanged coordinates stop accumulating velocity (DGC §3.1)
        hit = _decode_rows(jnp.ones_like(values, u_rows.dtype), indices,
                           d_row, u_rows.dtype)
        keep = 1.0 - jnp.clip(hit, 0.0, 1.0)
        new_v_rows = v_rows * keep
    return values, indices, new_e_rows, new_v_rows


def compress_worker(g: jax.Array, e: jax.Array, spec: CompressorSpec,
                    ratio: float, model_size: int, key, *,
                    codec_dtype=None, momentum: float = 0.0,
                    v: Optional[jax.Array] = None, backend: str = "auto"):
    """One worker's error-feedback compression of one gradient leaf.

    ``g`` is the leaf-shaped local gradient, ``e`` the ``(d_pad,)`` flat
    residual (and ``v`` the DGC velocity when ``momentum > 0``).

    Returns ``(values, indices, new_e, new_v)`` with ``values/indices``
    of shape ``(model_size, k_cap_row)`` and the conservation invariant
    ``decode(values, indices) + new_e == e + pad(g)`` (resp. ``e + v``
    under momentum correction) holding row-wise by construction.

    The pairs follow the ``core.codec`` contract: unused slots are
    sentinel-padded with value 0, real indices are duplicate-free, and a
    selector masking more than ``k_cap_row`` elements is truncated by
    ``compact_by_mask`` with the surplus mass landing in ``new_e`` (the
    conservation identity makes overflow lossy only for one step).  With
    ``codec_dtype`` the down-cast error is likewise decoded into
    ``new_e``, so the wire stays Eq.-2 exact.

    ``backend`` routes fused-capable compressors through the
    ``kernels/ef_fused`` pipeline (momentum correction needs the
    velocity update on materialized ``u`` and always takes the
    reference path).
    """
    d = g.size
    d_pad, d_row, k_row, k_cap = leaf_plan(d, model_size, ratio, spec)
    g_flat = jnp.pad(g.reshape(-1), (0, d_pad - d)).astype(e.dtype)
    values, indices, new_e_rows, new_v_rows = _compress_rows(
        g_flat.reshape(model_size, d_row), e.reshape(model_size, d_row),
        spec, k_row, k_cap, key, codec_dtype=codec_dtype, momentum=momentum,
        v_rows=(v.reshape(model_size, d_row) if momentum > 0.0 else None),
        backend=backend)
    new_e = new_e_rows.reshape(-1).astype(e.dtype)
    new_v = (new_v_rows.reshape(-1).astype(e.dtype)
             if new_v_rows is not None else None)
    return values, indices, new_e, new_v


# ---------------------------------------------------------------------------
# adaptive-density worker path (pure pieces: unit-testable without a mesh)
# ---------------------------------------------------------------------------


def _stats_reduce(row_stats):
    """Leaf-level ``(s, sq, mx)`` reduction of per-row pass-A tuples —
    the adaptk allocation signal's input (shared by both granularities)."""
    s = sum(st[0] for st in row_stats)
    sq = sum(st[1] for st in row_stats)
    mx = jnp.max(jnp.stack([st[2] for st in row_stats]))
    return s, sq, mx


def pass_a_stats_rows(g_rows: jax.Array, e_rows: jax.Array, name: str,
                      fused: bool):
    """Per-row pass-A statistics of ``u = g + e`` for one leaf.

    Returns ``(row_stats, (s, sq, mx))``: ``row_stats`` is the list of
    per-row ``fused_pass_a`` tuples to hand back to the fused pipeline
    (``None`` on the reference backend — its threshold recomputes from
    ``u`` directly), and the second element is the leaf-level reduction
    feeding ``adaptk.leaf_signal``.  Zero-padding contributes nothing to
    ``s``/``sq``/``mx``, so the leaf moments are exact for the true
    (unpadded) leaf.
    """
    if fused:
        row_stats = rows_pass_a(g_rows, e_rows, name)
        return row_stats, _stats_reduce(row_stats)
    u = g_rows.astype(jnp.result_type(g_rows.dtype, e_rows.dtype)) + e_rows
    return None, (jnp.sum(u), jnp.sum(u * u), jnp.max(jnp.abs(u)))


@jax.named_scope("ef.residual")
def _compress_rows_dynamic(g_rows: jax.Array, e_rows: jax.Array,
                           spec: CompressorSpec, k, k_cap: int, key, *,
                           codec_dtype=None, backend: str = "auto",
                           row_stats=None):
    """Row-level dynamic-k EF compression (traced per-leaf budget ``k``)
    — shared by :func:`compress_worker_dynamic` and the bucketed path."""
    model_size, d_row = g_rows.shape
    k_row = jnp.clip((k + model_size - 1) // model_size, 1, d_row)
    if resolve_backend(backend, spec):
        return _compress_rows_fused(g_rows, e_rows, spec, k_row, k_cap,
                                    codec_dtype, row_stats)
    u_rows = (g_rows.astype(jnp.result_type(g_rows.dtype, e_rows.dtype))
              + e_rows)
    if spec.needs_key:
        keys = jax.random.split(key, model_size)
        values, indices = jax.vmap(
            lambda r, kk: adaptk.select_dynamic(spec, r, k_row, k_cap, kk))(
                u_rows, keys)
    else:
        values, indices = jax.vmap(
            lambda r: adaptk.select_dynamic(spec, r, k_row, k_cap))(u_rows)
    if codec_dtype is not None:
        values = values.astype(codec_dtype)
    decoded = _decode_rows(values, indices, d_row, u_rows.dtype)
    return values, indices, u_rows - decoded


def compress_worker_dynamic(g_flat: jax.Array, e: jax.Array,
                            spec: CompressorSpec, k, model_size: int, key, *,
                            k_cap: int, codec_dtype=None,
                            backend: str = "auto", row_stats=None):
    """``compress_worker`` with a *traced* per-leaf element budget ``k``.

    ``g_flat`` is the already flat-padded ``(d_pad,)`` accumulation
    target (aggregate pads once, during the stats phase) and ``e`` the
    matching residual.  The leaf budget splits over model shards the
    same way as the static path — ``k_row = ceil(k / model_size)`` —
    except the ceil now runs in traced int32; the codec capacity
    ``k_cap`` is the static ceiling-derived row capacity from
    ``leaf_plan_adaptive``, which bounds ``k_row`` by construction.

    Returns ``(values, indices, new_e)`` with the same Eq. (2)
    conservation and sentinel-codec contracts as ``compress_worker``
    (property-tested in tests/test_properties.py); DGC momentum
    correction is fixed-k only and handled by the caller.
    """
    d_row = g_flat.size // model_size
    values, indices, new_e_rows = _compress_rows_dynamic(
        g_flat.reshape(model_size, d_row), e.reshape(model_size, d_row),
        spec, k, k_cap, key, codec_dtype=codec_dtype, backend=backend,
        row_stats=row_stats)
    return values, indices, new_e_rows.reshape(-1).astype(e.dtype)


# ---------------------------------------------------------------------------
# gTop-k recursive doubling (pure pieces: unit-testable without a mesh)
# ---------------------------------------------------------------------------


def encode_rows_topk(dense_rows: jax.Array, k_cap: int, codec_dtype=None):
    """Re-encode a dense ``(model_size, d_row)`` partial as fixed-capacity
    ``(model_size, k_cap)`` codec pairs — the gTop-k merge re-selection.

    Per row: exact top-``k_cap`` by magnitude.  When a row holds fewer
    than ``k_cap`` nonzeros the surplus slots carry real (non-sentinel)
    indices with value 0 — decode scatters zeros, so they are harmless
    padding; when it holds more, the smallest-magnitude surplus is
    dropped and the caller must fold ``dense_rows - decode(result)``
    back into a residual to keep Eq. (2) conservation.  ``codec_dtype``
    down-casts the value half of the wire exactly like
    ``compress_worker``.
    """
    def enc(row):
        _, idx = jax.lax.top_k(jnp.abs(row), k_cap)
        idx = idx.astype(jnp.int32)
        return row[idx], idx

    values, indices = jax.vmap(enc)(dense_rows)
    if codec_dtype is not None:
        values = values.astype(codec_dtype)
    return values, indices


def encode_bucket_topk(dense_bucket: jax.Array, layout: BucketLayout,
                       codec_dtype=None):
    """Per-segment gTop-k re-selection over the packed bucket, merged
    into ONE ``(model_size, k_cap_total)`` wire block with bucket-global
    indices.  Each segment's re-encode is exactly
    :func:`encode_rows_topk` on its own column range — bit-identical to
    the per-leaf merge — only the message is concatenated."""
    vs, is_ = [], []
    for s in layout.segments:
        v, i = encode_rows_topk(
            dense_bucket[:, s.row_off:s.row_off + s.d_row], s.k_cap,
            codec_dtype)
        vs.append(v)
        is_.append(codec.offset_indices(i, s.row_off))
    return jnp.concatenate(vs, axis=1), jnp.concatenate(is_, axis=1)


def gtopk_round_plan(axis_sizes):
    """Static recursive-doubling schedule over the joint data world.

    ``axis_sizes`` are the data-axis sizes in mesh order (e.g. ``(pod,
    data)``); the joint rank is row-major, so the *last* axis carries the
    low bits and halving walks axes from last to first.  Returns
    ``[(axis_pos, xor_mask, group_size), ...]`` — one entry per round,
    where ``group_size = 2**round`` is how many workers already share an
    identical partial when the round starts (the divisor for crediting
    that round's re-selection drop exactly once across replicas).

    Every axis size must be a power of two (raises otherwise).
    """
    plan = []
    group = 1
    for pos in range(len(axis_sizes) - 1, -1, -1):
        n = axis_sizes[pos]
        _log2_exact(n, f"data axis size (axis {pos})")
        mask = 1
        while mask < n:
            plan.append((pos, mask, group))
            group *= 2
            mask *= 2
    return plan


@jax.named_scope("wire")
def _gtopk_reduce_rounds(values, indices, axes, d_row: int, encode,
                         dtype=jnp.float32):
    """The recursive-doubling XOR-merge loop shared by both dispatch
    granularities — ONE implementation of the subtlest invariant in the
    wire (the drop/group crediting of DESIGN.md §7), parametrized only
    by the re-encode step ``encode(dense) -> (values, indices)``."""
    sizes = [jax.lax.axis_size(a) for a in axes]
    plan = gtopk_round_plan(sizes)
    dense = _decode_rows(values, indices, d_row, dtype)
    drop = jnp.zeros_like(dense)
    for r, (pos, mask, group) in enumerate(plan):
        if r == 0:
            # the worker's own pair already IS the top-k_cap encoding of
            # its partial (<= k_cap duplicate-free slots, values already
            # wire-cast), so the round-0 re-encode would reproduce it
            # with drop == 0 — send it as-is
            v, i, sent = values, indices, dense
        else:
            v, i = encode(dense)
            sent = _decode_rows(v, i, d_row, dtype)
            drop = drop + (dense - sent) / group
        perm = [(j, j ^ mask) for j in range(sizes[pos])]
        rv = jax.lax.ppermute(v, axes[pos], perm)
        ri = jax.lax.ppermute(i, axes[pos], perm)
        dense = sent + _decode_rows(rv, ri, d_row, dtype)
    return dense, drop


def _gtopk_reduce(values, indices, axes, d_row: int, k_cap: int,
                  codec_dtype=None, dtype=jnp.float32):
    """Recursive-doubling pruned-sum of every worker's codec pairs.

    Runs inside the shard_map manual region.  Each round: re-encode the
    local dense partial (top-``k_cap`` per row), exchange the codec with
    the XOR partner via a single-axis ppermute, decode-add.  After
    ``log2(W)`` rounds every worker holds the identical pruned sum.

    Returns ``(dense_sum, drop)``, both ``(model_size, d_row)``:
    ``dense_sum`` is the merged (pruned) sum of all workers'
    contributions, ``drop`` this worker's residual credit — each merge
    drop divided by the number of workers that performed that identical
    merge, so summing ``drop`` over the world recovers the total dropped
    mass exactly (DESIGN.md §7).
    """
    return _gtopk_reduce_rounds(
        values, indices, axes, d_row,
        lambda dense: encode_rows_topk(dense, k_cap, codec_dtype), dtype)


def _gtopk_reduce_bucket(values, indices, axes, layout: BucketLayout,
                         codec_dtype=None, dtype=jnp.float32):
    """Bucketed recursive doubling: the SAME XOR-partner merge tree as
    :func:`_gtopk_reduce`, but every round exchanges ONE merged
    ``(model_size, k_cap_total)`` wire block — ``log2(W)`` ppermute
    rounds per step TOTAL, not per leaf.  Re-selection stays per segment
    (:func:`encode_bucket_topk`), and segment index ranges are disjoint,
    so every decode/merge/drop is elementwise identical to the per-leaf
    reducer."""
    return _gtopk_reduce_rounds(
        values, indices, axes, layout.d_row_total,
        lambda dense: encode_bucket_topk(dense, layout, codec_dtype),
        dtype)


def gtopk_simulate(partials, k_cap: int, codec_dtype=None):
    """Single-process reference of ``_gtopk_reduce`` (no mesh, no
    collectives): the same XOR-partner merge tree over a list of
    ``(model_size, d_row)`` dense partials, one per worker.

    Returns ``(final, drops)`` — ``final`` the pruned sum every worker
    converges to, ``drops`` the per-worker residual credits.  Operation
    order matches the distributed path exactly (own decoded codec +
    received decoded codec), so the distributed result must agree to
    float tolerance; used as the equivalence oracle in
    tests/_dist_check.py and tests/test_dist_aggregate.py.
    """
    W = len(partials)
    _log2_exact(W)
    d_row = partials[0].shape[-1]
    dtype = partials[0].dtype
    partials = list(partials)
    drops = [jnp.zeros_like(partials[0]) for _ in range(W)]
    mask, group = 1, 1
    while mask < W:
        sent = []
        for w in range(W):
            v, i = encode_rows_topk(partials[w], k_cap, codec_dtype)
            sent.append(_decode_rows(v, i, d_row, dtype))
            drops[w] = drops[w] + (partials[w] - sent[w]) / group
        partials = [sent[w] + sent[w ^ mask] for w in range(W)]
        mask *= 2
        group *= 2
    return partials[0], drops


# ---------------------------------------------------------------------------
# mesh-level aggregation (call inside shard_map, manual over data axes)
# ---------------------------------------------------------------------------


@jax.named_scope("wire")
def aggregate_dense(grads, data_axes):
    """Dense-SGD baseline: plain mean over the data axes."""
    axes = tuple(data_axes)
    return jax.tree.map(lambda g: jax.lax.pmean(g, axes), grads)


def decode_width(d_row: int) -> int:
    """Width to decode a bucket's mean into: ``d_row`` rounded up to the
    TPU's tile of a 1-D f32 array, 1024 elements (8 x 128).  The chip's
    compiler decodes into a 1-D buffer; at a width off that tile, viewing
    it as the ``(model_size, d_row)`` bucket is a relayout, which it runs
    as a copy loop holding two more bucket-sized buffers.  The columns
    past ``d_row`` stay zero, and ``unpack_tree`` reads segments only."""
    return -(-d_row // 1024) * 1024


@jax.named_scope("wire")
def _gather_mean(values, indices, axis, n: int, d_row: int, dtype):
    """All-gather fixed-capacity pairs over ``axis`` and decode-average.

    Returns the ``(model_size, d_row)`` mean of all ``n`` participants'
    decoded contributions (identical on every participant).
    """
    v_all, i_all = jax.lax.all_gather((values, indices), axis)
    decoded = jax.vmap(
        lambda v, i: _decode_rows(v, i, d_row, dtype))(v_all, i_all)
    return jnp.sum(decoded, axis=0) / n


def _wire_config(strategy: str, axes, resid2, world: int,
                 mc: float, adaptive: bool, spec: CompressorSpec):
    """Validate the wire configuration (single source for both dispatch
    granularities).  ``strategy`` arrives already normalized — the config
    layer (``CompressionConfig`` / ``resolve_strategy``) owns the
    vocabulary.  Returns ``(strategy, hier, gtopk, outer_gtopk,
    outer_axis, inner_axes, n_pods, n_inner, world)``.

    ``hier`` selects the two-level pod -> global split (strategies
    ``"hierarchical"`` and ``"hier_gtopk"``); ``outer_gtopk`` further
    selects the hybrid's recursive-doubling merge across the pod axis
    in place of the pod-level gather/average."""
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown strategy {strategy!r}; have {STRATEGIES}")
    if adaptive and mc > 0.0:
        raise ValueError("momentum_correction is fixed-k only (the DGC "
                         "velocity update needs the static-k path); "
                         "disable it or density_policy")
    if adaptive and not adaptk.supports_dynamic(spec):
        raise ValueError(
            f"compressor {spec.name!r} bakes its per-step budget k into "
            f"static sample/candidate shapes, so it has no dynamic-k path; "
            f"adaptive density supports {adaptk.DYNAMIC_COMPRESSORS}.  Run "
            f"{spec.name!r} fixed-k instead: drop --density-policy on the "
            f"CLI (density_policy=None here)")
    # without a second residual the two-level path cannot run; fall back
    # to the flat gather over ALL data axes rather than silently dropping
    # the outer (pod) contribution
    hier = (strategy in ("hierarchical", "hier_gtopk") and len(axes) > 1
            and resid2 is not None)
    if strategy in ("hierarchical", "hier_gtopk") and not hier:
        strategy = "allgather"
    outer_gtopk = strategy == "hier_gtopk"
    gtopk = strategy == "gtopk"
    if gtopk:
        # the reducer's round count must match the actual mesh, so derive
        # the world from the bound axes rather than trusting the caller's
        # ``world`` (whose default of 1 would silently skip the rounds)
        world = 1
        for a in axes:
            world *= jax.lax.axis_size(a)
        _log2_exact(world)
    if mc > 0.0 and hier:
        raise ValueError("momentum_correction reuses resid2 as the DGC "
                         "velocity state; combine it with the flat or "
                         "gtopk path, not hierarchical aggregation")
    if mc > 0.0 and resid2 is None:
        raise ValueError("momentum_correction needs a velocity state: "
                         "init_train_state allocates resid2 whenever "
                         "momentum_correction > 0 (or "
                         "strategy='hierarchical') in its compression "
                         "config")
    if hier:
        outer_axis, inner_axes = axes[0], axes[1:]
        n_pods = jax.lax.axis_size(outer_axis)
        n_inner = max(1, world // n_pods)
        if outer_gtopk:
            # the hybrid's outer merge is the recursive-doubling tree,
            # so the pod count must halve exactly at every round
            _log2_exact(n_pods, "pod-axis size")
    else:
        outer_axis, inner_axes = None, axes
        n_pods, n_inner = 1, world
    return strategy, hier, gtopk, outer_gtopk, outer_axis, inner_axes, \
        n_pods, n_inner, world


def _adaptive_allocation(adapt_state, sigs, sqs, dims, ratio, policy, step,
                         lo, hi, axes):
    """Phase 2 of the adaptive path — ONE implementation shared by all
    three dispatch granularities: pmean the stacked per-leaf signal over
    the data axes (one identical allocation on every worker), EMA-blend,
    derive the global budget (× DGC warmup, × the global-k controller's
    norm-decay scale when enabled — DESIGN.md §12) and split it
    budget-exactly.

    The controller's Σu² observation rides the SAME pmean as one extra
    lane appended to the stacked signal — pmean is elementwise, so the
    existing lanes (and with them every non-globalk jaxpr and its CI
    dispatch-count pins) are bit-untouched, and the controller costs no
    extra collective.  Returns ``(k_alloc, K_eff, new_adapt_state)``.
    """
    globalk = policy.global_policy != "none"
    stack = jnp.stack(sigs)
    if globalk:
        sq_tot = jnp.asarray(sum(sqs), jnp.float32).reshape(1)
        stack = jnp.concatenate([stack, sq_tot])
    red = jax.lax.pmean(stack, axes)
    signal = red[:-1] if globalk else red
    signal, new_adapt = adaptk.blend_signal(adapt_state, signal, policy.ema)
    K = adaptk.budget(dims, ratio, policy, step)
    if globalk:
        scale, upd = adaptk.global_scale(
            new_adapt if new_adapt is not None else adapt_state,
            red[-1], policy)
        K = adaptk.scale_budget(K, scale)
        if new_adapt is not None:
            new_adapt = {**new_adapt, **upd}
    k_alloc, K_eff = adaptk.allocate(K, signal, lo, hi)
    return k_alloc, K_eff, new_adapt


def aggregate_compressed(grads, resid, config, *args, resid2=None,
                         world: int = 1, adapt_state=None, step=None,
                         **legacy):
    """Eq. (2) sparse aggregation of a gradient pytree — per-leaf loop.

    Config-first signature::

        aggregate_compressed(grads, resid, config, data_axes, model_axis,
                             model_size, key, *, resid2=None, world=1,
                             adapt_state=None, step=None)

    ``config`` is a :class:`~repro.core.compression.CompressionConfig`
    carrying compressor/ratio/strategy/codec_dtype/momentum_correction/
    backend/density_policy; mesh geometry (``data_axes``, ``model_axis``,
    ``model_size``) and runtime state (``resid2``, ``world``,
    ``adapt_state``, ``step``) stay per-call.  The legacy spelling —
    a ``CompressorSpec`` + ``ratio`` positionals with loose
    ``strategy=``/``hierarchical=``/... kwargs — still works but emits a
    ``DeprecationWarning`` and forwards through the same config.

    ``config.strategy`` picks the wire pattern (module docstring,
    DESIGN.md §3, §7): ``"allgather"`` (flat, O(W) pairs),
    ``"hierarchical"`` (two-level pod -> global, needs ``resid2`` and
    >= 2 data axes — falls back to flat otherwise), or ``"gtopk"``
    (recursive doubling, O(log W) pairs, needs power-of-two data-axis
    sizes).

    Returns an :class:`AggregateResult` ``(agg, resid, resid2,
    adapt_state, metrics)``; ``agg`` has the gradient's tree/shape/dtype,
    residual trees are flat-padded like ``init_residuals``.  ``metrics``
    are replicated scalars: ``density`` (measured nnz fraction),
    ``comm_bits_sparse`` / ``comm_bits_dense`` (per-worker wire volume,
    compile-time constants), ``wire_bytes`` and ``collectives_per_step``
    (the dispatch count this granularity pays — L per wire level here;
    see :func:`aggregate_bucketed` for the 1-per-level pipeline).

    ``config.backend`` selects the per-worker compression pipeline
    (``"auto"``/``"fused"``/``"reference"``, DESIGN.md §8) for every
    wire strategy — it changes HBM passes, never wire or Eq.-2
    semantics.

    ``config.density_policy`` (a ``core.adaptk.DensityPolicy``) switches
    every leaf to the adaptive-density path (DESIGN.md §9): pass A of the
    fused pipeline runs first for every leaf, the per-leaf moments are
    pmean'd over the data axes (one identical allocation on every
    worker), and the global budget ``K_total(step)`` is redistributed
    into per-leaf traced budgets by ``adaptk.allocate`` — budget-exact
    under the policy's floor/ceiling clamps.  Codec capacities, staging
    widths and the wire volume stay the compile-time constants derived
    from the ceiling clamp.  ``adapt_state`` carries the EMA controller
    state (lives in TrainState; ``None`` = stateless) and is returned
    updated; ``step`` feeds the DGC warmup schedule.  Adaptive mode
    requires a ``DYNAMIC_COMPRESSORS`` member and is mutually exclusive
    with ``momentum_correction``.
    """
    if isinstance(config, CompressorSpec):
        if "ratio" in legacy:
            ratio = legacy.pop("ratio")
        else:
            ratio, args = args[0], args[1:]
        config = _config_from_legacy("aggregate_compressed", config, ratio,
                                     legacy)
    else:
        config = _require_config("aggregate_compressed", config, legacy)
    data_axes, model_axis, model_size, key = args
    return _aggregate_compressed(grads, resid, config, data_axes,
                                 model_axis, model_size, key, resid2=resid2,
                                 world=world, adapt_state=adapt_state,
                                 step=step)


def _aggregate_compressed(grads, resid, config: CompressionConfig,
                          data_axes, model_axis: str, model_size: int, key,
                          *, resid2, world: int, adapt_state, step):
    spec, ratio = config.spec, config.ratio
    codec_dtype = config.codec_dtype
    backend = config.backend
    density_policy = config.density_policy
    axes = tuple(data_axes)
    mc = float(config.momentum_correction)
    adaptive = density_policy is not None
    strategy, hier, gtopk, outer_gtopk, outer_axis, inner_axes, n_pods, \
        n_inner, world = _wire_config(config.strategy, axes, resid2, world,
                                      mc, adaptive, spec)
    use_v = mc > 0.0

    path_leaves, treedef = jax.tree_util.tree_flatten_with_path(grads)
    g_leaves = [leaf for _, leaf in path_leaves]
    salts = [leaf_key_salt(leaf_path_name(path)) for path, _ in path_leaves]
    e_leaves = treedef.flatten_up_to(resid)
    r2_leaves = (treedef.flatten_up_to(resid2) if resid2 is not None
                 else [None] * len(g_leaves))

    # -- adaptive phase 1: pass-A stats -> pmean'd signal -> allocation --
    new_adapt = adapt_state
    k_alloc = K_eff = None
    plans, g_flats, leaf_row_stats = {}, {}, {}
    if adaptive:
        fusedp = resolve_backend(backend, spec)
        sigs, sqs = [], []
        for li, (g, e) in enumerate(zip(g_leaves, e_leaves)):
            plan = leaf_plan_adaptive(g.size, model_size, ratio, spec,
                                      density_policy)
            d_pad, d_row = plan[0], plan[1]
            g_flat = jnp.pad(g.reshape(-1),
                             (0, d_pad - g.size)).astype(e.dtype)
            row_stats, (s, sq, mx) = pass_a_stats_rows(
                g_flat.reshape(model_size, d_row),
                e.reshape(model_size, d_row), spec.name, fusedp)
            sigs.append(adaptk.leaf_signal(density_policy.policy, g.size,
                                           s, sq, mx))
            sqs.append(sq)
            plans[li], g_flats[li], leaf_row_stats[li] = plan, g_flat, \
                row_stats
        k_alloc, K_eff, new_adapt = _adaptive_allocation(
            adapt_state, sigs, sqs, [g.size for g in g_leaves], ratio,
            density_policy, step,
            [plans[li][2] for li in range(len(g_leaves))],
            [plans[li][3] for li in range(len(g_leaves))], axes)
    else:
        for li, g in enumerate(g_leaves):
            plans[li] = leaf_plan(g.size, model_size, ratio, spec)

    # -- loop-invariant wire accounting, hoisted out of the leaf loop --
    val_bits = jnp.dtype(codec_dtype).itemsize * 8 if codec_dtype else 32
    d_total = sum(g.size for g in g_leaves)
    cap_total = model_size * sum(plans[li][-1]
                                 for li in range(len(g_leaves)))
    levels = strategy_wire_pairs(strategy, world, n_pods)
    bits_sparse = float(levels * cap_total * (val_bits + 32))
    bits_dense = float(sum(2 * g.size * jnp.dtype(g.dtype).itemsize * 8
                           for g in g_leaves))
    nnz_local = jnp.zeros((), jnp.float32)

    agg_leaves, new_e_leaves, new_r2_leaves = [], [], []
    for li, (g, e, r2) in enumerate(zip(g_leaves, e_leaves, r2_leaves)):
        lkey = jax.random.fold_in(key, salts[li])
        d = g.size
        if adaptive:
            d_pad, d_row, _, _, k_cap = plans[li]
            values, indices, new_e = compress_worker_dynamic(
                g_flats[li], e, spec, k_alloc[li], model_size, lkey,
                k_cap=k_cap, codec_dtype=codec_dtype, backend=backend,
                row_stats=leaf_row_stats[li])
            new_v = None
        else:
            d_pad, d_row, k_row, k_cap = plans[li]
            values, indices, new_e, new_v = compress_worker(
                g, e, spec, ratio, model_size, lkey,
                codec_dtype=codec_dtype,
                momentum=mc if use_v else 0.0, v=r2 if use_v else None,
                backend=backend)
        nnz_local += codec.nnz(indices).astype(jnp.float32)

        if gtopk:
            dense_sum, merge_drop = _gtopk_reduce(
                values, indices, axes, d_row, k_cap, codec_dtype)
            mean = dense_sum / world
            # mass pruned by the merge re-selections returns to this
            # worker's residual (scaled so the world sums it exactly once)
            new_e = (new_e + merge_drop.reshape(-1).astype(new_e.dtype))
        else:
            mean = _gather_mean(values, indices, inner_axes, n_inner,
                                d_row, jnp.float32)

        if hier:
            # second level: compress the pod-mean against resid2 and
            # average across pods (identical on every worker of a pod)
            if adaptive:
                # same per-leaf budget as level 1 (its pass-A stats are
                # the pod-mean's own — computed inside the pipeline)
                v2, i2, new_r2 = compress_worker_dynamic(
                    mean.reshape(-1).astype(r2.dtype), r2, spec,
                    k_alloc[li], model_size, jax.random.fold_in(lkey, 1),
                    k_cap=k_cap, codec_dtype=codec_dtype, backend=backend)
            elif resolve_backend(backend, spec):
                v2, i2, r2_rows = _compress_rows_fused(
                    mean, r2.reshape(model_size, d_row), spec, k_row,
                    k_cap, codec_dtype)
                new_r2 = r2_rows.reshape(-1).astype(r2.dtype)
            else:
                u2 = r2 + mean.reshape(-1)
                v2, i2 = _select_rows(spec, u2.reshape(model_size, d_row),
                                      k_row, jax.random.fold_in(lkey, 1))
                if codec_dtype is not None:
                    v2 = v2.astype(codec_dtype)
                new_r2 = (u2.reshape(model_size, d_row) -
                          _decode_rows(v2, i2, d_row, jnp.float32)
                          ).reshape(-1).astype(r2.dtype)
            if outer_gtopk:
                # hybrid outer level: gTop-k recursive doubling across
                # the pod axis.  Merge drop is credited to resid2
                # UN-divided by n_pods — resid2 is pod-replicated, so
                # summing one representative worker per pod recovers the
                # dropped mass exactly once (same convention as the
                # pod-level residual itself)
                dense2, drop2 = _gtopk_reduce(
                    v2, i2, (outer_axis,), d_row, k_cap, codec_dtype)
                mean = dense2 / n_pods
                new_r2 = new_r2 + drop2.reshape(-1).astype(new_r2.dtype)
            else:
                mean = _gather_mean(v2, i2, outer_axis, n_pods, d_row,
                                    jnp.float32)
            nnz_local += codec.nnz(i2).astype(jnp.float32)
        elif use_v:
            new_r2 = new_v
        else:
            new_r2 = r2

        agg_leaves.append(
            mean.reshape(-1)[:d].reshape(g.shape).astype(g.dtype))
        new_e_leaves.append(new_e)
        new_r2_leaves.append(new_r2)

    metrics = {
        "density": jax.lax.pmean(nnz_local / d_total, axes),
        "density_cap": jnp.float32(cap_total / d_total),
        "comm_bits_sparse": jnp.float32(bits_sparse),
        "comm_bits_dense": jnp.float32(bits_dense),
        "wire_bytes": jnp.float32(bits_sparse / 8.0),
        "collectives_per_step": jnp.float32(collective_count(
            strategy, world, n_pods, leaves=len(g_leaves))),
    }
    if adaptive:
        # identical on every worker: the allocation ran on the pmean'd
        # signal (budget exactness: k_total == clip of the configured
        # budget into the policy's [floor, ceiling] sums)
        metrics["k_total"] = K_eff.astype(jnp.float32)
    new_resid = treedef.unflatten(new_e_leaves)
    new_resid2 = (treedef.unflatten(new_r2_leaves)
                  if resid2 is not None else None)
    return AggregateResult(treedef.unflatten(agg_leaves), new_resid,
                           new_resid2, new_adapt, metrics)


# ---------------------------------------------------------------------------
# bucketed aggregation: one wire message per step (DESIGN.md §10)
# ---------------------------------------------------------------------------


def bucket_compress(G: jax.Array, E: jax.Array, layout: BucketLayout,
                    spec: CompressorSpec, key, *, codec_dtype=None,
                    momentum: float = 0.0, V=None, backend: str = "auto",
                    k_alloc=None, seg_stats=None, key_fold=None):
    """Worker-local EF compression of the packed bucket — pure
    (unit-testable without a mesh).

    ``G``/``E`` (and ``V`` under momentum correction) are
    ``(model_size, d_row_total)`` buckets; returns ``(values, indices,
    new_E, new_V)`` where ``values``/``indices`` are ONE concatenated
    ``(model_size, k_cap_total)`` codec pair with bucket-global indices
    and ``new_E`` the residual bucket.  Selection runs per leaf segment
    with the segment's own static plan and the stable per-segment RNG
    salt fold — bit-identical to :func:`compress_worker` /
    :func:`compress_worker_dynamic` on the same leaf values.

    ``k_alloc`` switches to the adaptive dynamic-k path (traced
    per-segment element budgets, ``seg_stats`` the per-segment pass-A
    row stats); ``key_fold`` appends an extra ``fold_in`` after the salt
    (the hierarchical second level folds 1, matching the per-leaf path).
    """
    segs = layout.segments
    fused = momentum == 0.0 and resolve_backend(backend, spec)
    adaptive = k_alloc is not None
    vals, idcs, new_e_blocks, new_v_blocks = [], [], [], []

    def seg_key(s):
        if key is None:
            return None
        lkey = jax.random.fold_in(key, s.salt)
        return lkey if key_fold is None else jax.random.fold_in(lkey,
                                                                key_fold)

    if fused:
        M = layout.model_size
        ranges = [(s.row_off, s.d_row) for s in segs]
        if adaptive:
            ks = [jnp.clip((k_alloc[si] + M - 1) // M, 1, s.d_row)
                  for si, s in enumerate(segs)]
        else:
            ks = [s.k_row for s in segs]
        triples = segmented_compress_ef(G, E, ranges, spec.name, ks,
                                        [s.k_cap for s in segs],
                                        stats=seg_stats)
        for s, (v, i, ne) in zip(segs, triples):
            v, i, ne = _wire_cast_fixup(v, i, ne, codec_dtype)
            vals.append(v)
            idcs.append(codec.offset_indices(i, s.row_off))
            new_e_blocks.append(ne)
    else:
        for si, s in enumerate(segs):
            a, b = s.row_off, s.row_off + s.d_row
            if adaptive:
                v, i, ne = _compress_rows_dynamic(
                    G[:, a:b], E[:, a:b], spec, k_alloc[si], s.k_cap,
                    seg_key(s), codec_dtype=codec_dtype, backend=backend,
                    row_stats=None if seg_stats is None else seg_stats[si])
                nv = None
            else:
                v, i, ne, nv = _compress_rows(
                    G[:, a:b], E[:, a:b], spec, s.k_row, s.k_cap,
                    seg_key(s), codec_dtype=codec_dtype, momentum=momentum,
                    v_rows=V[:, a:b] if momentum > 0.0 else None,
                    backend=backend)
            vals.append(v)
            idcs.append(codec.offset_indices(i, s.row_off))
            new_e_blocks.append(ne)
            if nv is not None:
                new_v_blocks.append(nv)

    with jax.named_scope("bucket.pack"):
        values = jnp.concatenate(vals, axis=1)
        indices = jnp.concatenate(idcs, axis=1)
        new_E = jnp.concatenate(
            [blk.astype(E.dtype) for blk in new_e_blocks], axis=1)
        new_V = (jnp.concatenate(
            [blk.astype(E.dtype) for blk in new_v_blocks], axis=1)
            if new_v_blocks else None)
    return values, indices, new_E, new_V


def _segment_outcomes(indices: jax.Array, layout: BucketLayout,
                      spec: CompressorSpec, k_alloc=None):
    """Algorithm 1's outcome on one worker's ``(model_size,
    k_cap_total)`` wire block, read per leaf segment at its static
    column offset: ``(at_cap, under_band)``, float counts of segments.

    ``at_cap``: segments whose kept count equals their capacity
    ``model_size * k_cap`` — the selection over-ran the codec and its
    surplus (the highest indices) stayed in the residual.  Selectors
    that always fill their capacity (topk, randk, rtopk, dgck) count
    every segment here.  ``under_band``: for the compressors that run
    Algorithm 1's refinement (``spec.banded``: gaussiank, gaussiank2;
    not histk, whose one-pass threshold has no band), segments that
    kept fewer than ``model_size * ceil(2 k_row / 3)`` — the refinement
    ended under its accept band; 0 for the others.  ``k_alloc`` gives
    the adaptive path's traced per-segment budgets."""
    M = layout.model_size
    banded = spec.banded
    at_cap = under = jnp.zeros((), jnp.float32)
    for si, s in enumerate(layout.segments):
        kept = codec.nnz(indices[:, s.cap_off:s.cap_off + s.k_cap])
        at_cap += (kept == M * s.k_cap).astype(jnp.float32)
        if banded:
            k_row = (s.k_row if k_alloc is None else
                     jnp.clip((k_alloc[si] + M - 1) // M, 1, s.d_row))
            under += (kept < M * ((2 * k_row + 2) // 3)).astype(jnp.float32)
    return at_cap, under


def _replicated_outcomes(nnz_local, d_total: int, at_cap, under, axes):
    """``density``, ``ef_leaves_at_cap`` and ``ef_leaves_under_band``,
    averaged over the data axes in one ``pmean``."""
    density, at_cap, under = jax.lax.pmean(
        jnp.stack([nnz_local / d_total, at_cap, under]), axes)
    return {"density": density, "ef_leaves_at_cap": at_cap,
            "ef_leaves_under_band": under}


def aggregate_bucketed(grads, resid, layout: BucketLayout, config,
                       *args, resid2=None, world: int = 1,
                       adapt_state=None, step=None, **legacy):
    """Eq. (2) sparse aggregation over the flat bucketed pipeline.

    Config-first signature::

        aggregate_bucketed(grads, resid, layout, config, data_axes,
                           model_axis, key, *, resid2=None, world=1,
                           adapt_state=None, step=None)

    Same semantics and return contract as :func:`aggregate_compressed`
    (bit-identical results — asserted by tests/_dist_check.py
    ``bucketed``), except the residuals are flat buckets
    (``(model_size * d_row_total,)``, see ``dist/layout.py``) and every
    wire level is exactly ONE collective per step regardless of leaf
    count:

      allgather      1 sparse all-gather     (per-leaf: L)
      hierarchical   1 per pod level = 2     (per-leaf: 2·L)
      gtopk          log2(W) ppermute rounds (per-leaf: L·log2(W))
      hier_gtopk     1 + log2(P) rounds      (per-leaf: L·(1+log2 P))

    ``ratio``/``model_size`` come from the layout (which must have been
    built for this config's ``spec`` and density mode — validated
    loudly).  The legacy spelling (a ``CompressorSpec`` in the config
    slot + loose kwargs) forwards with a ``DeprecationWarning``.
    Returns an :class:`AggregateResult` with flat-bucket residuals; its
    metrics add ``ef_leaves_at_cap`` and ``ef_leaves_under_band``
    (:func:`_segment_outcomes`, averaged over the data axes).
    """
    if isinstance(config, CompressorSpec):
        config = _config_from_legacy(
            "aggregate_bucketed", config,
            legacy.pop("ratio", layout.ratio), legacy)
    else:
        config = _require_config("aggregate_bucketed", config, legacy)
    data_axes, model_axis, key = args
    return _aggregate_bucketed(grads, resid, layout, config, data_axes,
                               model_axis, key, resid2=resid2, world=world,
                               adapt_state=adapt_state, step=step)


def _aggregate_bucketed(grads, resid, layout: BucketLayout,
                        config: CompressionConfig, data_axes,
                        model_axis: str, key, *, resid2, world: int,
                        adapt_state, step):
    spec = config.spec
    codec_dtype = config.codec_dtype
    backend = config.backend
    density_policy = config.density_policy
    axes = tuple(data_axes)
    mc = float(config.momentum_correction)
    adaptive = density_policy is not None
    if layout.spec_name != spec.name:
        raise ValueError(f"layout was built for compressor "
                         f"{layout.spec_name!r}, got {spec.name!r}")
    if layout.adaptive != adaptive:
        raise ValueError(
            f"layout adaptive={layout.adaptive} does not match "
            f"density_policy={'set' if adaptive else 'None'}; rebuild the "
            "layout with the matching density_policy")
    strategy, hier, gtopk, outer_gtopk, outer_axis, inner_axes, n_pods, \
        n_inner, world = _wire_config(config.strategy, axes, resid2, world,
                                      mc, adaptive, spec)

    M, D = layout.model_size, layout.d_row_total
    G = pack_grads(layout, grads, resid.dtype)
    E = resid.reshape(M, D)
    R2 = resid2.reshape(M, D) if resid2 is not None else None

    # -- adaptive phase 1: segmented pass-A -> pmean'd signal -> allocation
    new_adapt = adapt_state
    k_alloc = K_eff = None
    seg_stats = None
    if adaptive:
        fusedp = resolve_backend(backend, spec)
        sigs, sqs = [], []
        if fusedp:
            seg_stats = segmented_pass_a(
                G, E, [(s.row_off, s.d_row) for s in layout.segments],
                spec.name)
            for s, rs in zip(layout.segments, seg_stats):
                sm, sq, mx = _stats_reduce(rs)
                sigs.append(adaptk.leaf_signal(density_policy.policy,
                                               s.size, sm, sq, mx))
                sqs.append(sq)
        else:
            for s in layout.segments:
                a, b = s.row_off, s.row_off + s.d_row
                _, (sm, sq, mx) = pass_a_stats_rows(
                    G[:, a:b], E[:, a:b], spec.name, False)
                sigs.append(adaptk.leaf_signal(density_policy.policy,
                                               s.size, sm, sq, mx))
                sqs.append(sq)
        k_alloc, K_eff, new_adapt = _adaptive_allocation(
            adapt_state, sigs, sqs, [s.size for s in layout.segments],
            layout.ratio, density_policy, step,
            [s.k_lo for s in layout.segments],
            [s.k_hi for s in layout.segments], axes)

    # -- worker-local compression: ONE wire block --
    values, indices, new_E, new_V = bucket_compress(
        G, E, layout, spec, key, codec_dtype=codec_dtype, momentum=mc,
        V=R2 if mc > 0.0 else None, backend=backend, k_alloc=k_alloc,
        seg_stats=seg_stats)
    nnz_local = codec.nnz(indices).astype(jnp.float32)
    at_cap, under = _segment_outcomes(indices, layout, spec, k_alloc)

    # -- the wire: one collective per level --
    if gtopk:
        dense_sum, merge_drop = _gtopk_reduce_bucket(
            values, indices, axes, layout, codec_dtype)
        mean = dense_sum / world
        new_E = new_E + merge_drop.astype(new_E.dtype)
    else:
        mean = _gather_mean(values, indices, inner_axes, n_inner,
                            D if hier else decode_width(D), jnp.float32)

    if hier:
        # second level: compress the pod-mean bucket against resid2 and
        # average across pods — one more all-gather, not one per leaf
        g2 = mean.astype(R2.dtype) if adaptive else mean
        v2, i2, new_R2, _ = bucket_compress(
            g2, R2, layout, spec, key, codec_dtype=codec_dtype,
            backend=backend, k_alloc=k_alloc, key_fold=1)
        if outer_gtopk:
            # hybrid outer level: one gTop-k merge tree across the pod
            # axis per step; merge drop credited un-divided by n_pods
            # (pod-replicated resid2 — same convention as per-leaf)
            dense2, drop2 = _gtopk_reduce_bucket(
                v2, i2, (outer_axis,), layout, codec_dtype)
            mean = dense2 / n_pods
            new_R2 = new_R2 + drop2.astype(new_R2.dtype)
        else:
            mean = _gather_mean(v2, i2, outer_axis, n_pods,
                                decode_width(D), jnp.float32)
        nnz_local += codec.nnz(i2).astype(jnp.float32)
    elif mc > 0.0:
        new_R2 = new_V
    else:
        new_R2 = R2

    agg = unpack_tree(layout, mean, like=grads)
    # the dense baseline is sized from the RUNTIME gradient dtypes (not
    # the dtypes frozen into the layout at build time), matching the
    # per-leaf path under mixed-precision grads
    bits_dense = float(sum(2 * g.size * jnp.dtype(g.dtype).itemsize * 8
                           for g in jax.tree.leaves(grads)))
    metrics = {
        **_replicated_outcomes(nnz_local, layout.d_total, at_cap, under,
                               axes),
        "density_cap": jnp.float32(
            M * layout.k_cap_total / layout.d_total),
        "comm_bits_sparse": jnp.float32(
            layout.comm_bits_sparse(strategy, world, n_pods, codec_dtype)),
        "comm_bits_dense": jnp.float32(bits_dense),
        "wire_bytes": jnp.float32(
            layout.comm_bits_sparse(strategy, world, n_pods,
                                    codec_dtype) / 8.0),
        "collectives_per_step": jnp.float32(
            layout.collectives(strategy, world, n_pods)),
    }
    if adaptive:
        metrics["k_total"] = K_eff.astype(jnp.float32)
    new_resid2 = new_R2.reshape(-1) if resid2 is not None else None
    return AggregateResult(agg, new_E.reshape(-1), new_resid2, new_adapt,
                           metrics)


# ---------------------------------------------------------------------------
# chunked bucketed aggregation: overlap the wire with the backward pass
# (DESIGN.md §11)
# ---------------------------------------------------------------------------


def aggregate_bucketed_chunked(grads, resid, layout: BucketLayout,
                               plan: ChunkPlan, config, *args,
                               resid2=None, world: int = 1,
                               adapt_state=None, step=None, **legacy):
    """:func:`aggregate_bucketed` re-dispatched as ``plan.n_chunks``
    independent compress+wire chains — the overlapped schedule
    (DESIGN.md §11).

    Config-first signature::

        aggregate_bucketed_chunked(grads, resid, layout, plan, config,
                                   data_axes, model_axis, key, *,
                                   resid2=None, world=1,
                                   adapt_state=None, step=None)

    Identical semantics and BIT-identical results (asserted by
    tests/_dist_check.py ``chunked``): every chunk group runs the same
    per-segment selection, salting, residual update and wire arithmetic
    as its column window of the unchunked bucket, via
    :func:`layout.chunk_view` sub-layouts.  What changes is dataflow
    shape: chunk ``c``'s collective depends only on chunk ``c``'s
    gradient leaves and residual window, so when the train step's
    custom-vjp seam (train/step.py) releases chunk grads incrementally,
    chunk ``c``'s compress + collective can execute while chunk ``c+1``'s
    backward is still in flight — the double-buffered overlap.  The only
    cross-chunk barrier is the adaptive allocator, which needs every
    leaf's pass-A moments BEFORE any chunk's budget is final (one psum,
    not a wire message).

    Dispatch cost: ``plan.n_chunks`` collectives per wire level (N
    all-gathers / 2N for hierarchical / N·log2(W) gTop-k rounds /
    N·(1+log2 P) for hier_gtopk) —
    reported in ``metrics["collectives_per_step"]``; total wire volume
    is unchanged.  ``plan`` must tile this exact ``layout`` (validated
    loudly).  Returns an :class:`AggregateResult` with flat-bucket
    residuals."""
    if isinstance(config, CompressorSpec):
        config = _config_from_legacy(
            "aggregate_bucketed_chunked", config,
            legacy.pop("ratio", layout.ratio), legacy)
    else:
        config = _require_config("aggregate_bucketed_chunked", config,
                                 legacy)
    data_axes, model_axis, key = args
    return _aggregate_bucketed_chunked(
        grads, resid, layout, plan, config, data_axes, model_axis, key,
        resid2=resid2, world=world, adapt_state=adapt_state, step=step)


def _aggregate_bucketed_chunked(grads, resid, layout: BucketLayout,
                                plan: ChunkPlan,
                                config: CompressionConfig, data_axes,
                                model_axis: str, key, *, resid2,
                                world: int, adapt_state, step):
    spec = config.spec
    codec_dtype = config.codec_dtype
    backend = config.backend
    density_policy = config.density_policy
    axes = tuple(data_axes)
    mc = float(config.momentum_correction)
    adaptive = density_policy is not None
    if layout.spec_name != spec.name:
        raise ValueError(f"layout was built for compressor "
                         f"{layout.spec_name!r}, got {spec.name!r}")
    if layout.adaptive != adaptive:
        raise ValueError(
            f"layout adaptive={layout.adaptive} does not match "
            f"density_policy={'set' if adaptive else 'None'}; rebuild the "
            "layout with the matching density_policy")
    validate_chunk_plan(layout, plan)
    strategy, hier, gtopk, outer_gtopk, outer_axis, inner_axes, n_pods, \
        n_inner, world = _wire_config(config.strategy, axes, resid2, world,
                                      mc, adaptive, spec)

    M, D = layout.model_size, layout.d_row_total
    E = resid.reshape(M, D)
    R2 = resid2.reshape(M, D) if resid2 is not None else None

    g_leaves = jax.tree.leaves(grads)
    if len(g_leaves) != len(layout.segments):
        raise ValueError(f"tree has {len(g_leaves)} leaves, layout has "
                         f"{len(layout.segments)} segments")
    views = [chunk_view(layout, grp) for grp in plan.groups]
    # per-chunk packing: chunk c's bucket is built from chunk c's leaves
    # ONLY — the dataflow seam the overlap rides on (no edge from later
    # chunks' gradients into this chunk's compress or collective)
    Gs = [pack_grads(v, g_leaves[grp.seg_lo:grp.seg_hi], resid.dtype)
          for grp, v in zip(plan.groups, views)]
    Es = [E[:, grp.row_off:grp.row_off + grp.d_row] for grp in plan.groups]
    R2s = ([R2[:, grp.row_off:grp.row_off + grp.d_row]
            for grp in plan.groups] if R2 is not None
           else [None] * plan.n_chunks)

    # -- adaptive phase 1: per-chunk pass-A moments, ONE global allocation
    # BEFORE any chunk's wire dispatch.  Signals are gathered in global
    # segment order, so the pmean/blend/budget/allocate chain is the
    # same arithmetic on the same vector as the unchunked path.
    new_adapt = adapt_state
    k_alloc = K_eff = None
    chunk_stats = [None] * plan.n_chunks
    if adaptive:
        fusedp = resolve_backend(backend, spec)
        sigs, sqs = [], []
        for c, view in enumerate(views):
            if fusedp:
                stats = segmented_pass_a(
                    Gs[c], Es[c], [(s.row_off, s.d_row)
                                   for s in view.segments], spec.name)
                chunk_stats[c] = stats
                for s, rs in zip(view.segments, stats):
                    sm, sq, mx = _stats_reduce(rs)
                    sigs.append(adaptk.leaf_signal(density_policy.policy,
                                                   s.size, sm, sq, mx))
                    sqs.append(sq)
            else:
                for s in view.segments:
                    a, b = s.row_off, s.row_off + s.d_row
                    _, (sm, sq, mx) = pass_a_stats_rows(
                        Gs[c][:, a:b], Es[c][:, a:b], spec.name, False)
                    sigs.append(adaptk.leaf_signal(density_policy.policy,
                                                   s.size, sm, sq, mx))
                    sqs.append(sq)
        k_alloc, K_eff, new_adapt = _adaptive_allocation(
            adapt_state, sigs, sqs, [s.size for s in layout.segments],
            layout.ratio, density_policy, step,
            [s.k_lo for s in layout.segments],
            [s.k_hi for s in layout.segments], axes)

    # -- per-chunk compress + wire.  Below this point there are NO data
    # edges between chunks: XLA's scheduler is free to run chunk c's
    # collective while chunk c+1 is still compressing (double buffering
    # at the dataflow level; see DESIGN.md §11 for the CPU/interpret
    # caveat).
    means, new_E_blocks, new_R2_blocks = [], [], []
    nnz_local = at_cap = under = jnp.zeros((), jnp.float32)
    for c, (grp, view) in enumerate(zip(plan.groups, views)):
        ka = k_alloc[grp.seg_lo:grp.seg_hi] if adaptive else None
        values, indices, new_Ec, new_Vc = bucket_compress(
            Gs[c], Es[c], view, spec, key, codec_dtype=codec_dtype,
            momentum=mc, V=R2s[c] if mc > 0.0 else None, backend=backend,
            k_alloc=ka, seg_stats=chunk_stats[c])
        nnz_local += codec.nnz(indices).astype(jnp.float32)
        at_cap_c, under_c = _segment_outcomes(indices, view, spec, ka)
        at_cap, under = at_cap + at_cap_c, under + under_c

        if gtopk:
            dense_sum, merge_drop = _gtopk_reduce_bucket(
                values, indices, axes, view, codec_dtype)
            mean_c = dense_sum / world
            new_Ec = new_Ec + merge_drop.astype(new_Ec.dtype)
        else:
            mean_c = _gather_mean(values, indices, inner_axes, n_inner,
                                  view.d_row_total, jnp.float32)

        if hier:
            g2 = mean_c.astype(R2.dtype) if adaptive else mean_c
            v2, i2, new_R2c, _ = bucket_compress(
                g2, R2s[c], view, spec, key, codec_dtype=codec_dtype,
                backend=backend, k_alloc=ka, key_fold=1)
            if outer_gtopk:
                dense2, drop2 = _gtopk_reduce_bucket(
                    v2, i2, (outer_axis,), view, codec_dtype)
                mean_c = dense2 / n_pods
                new_R2c = new_R2c + drop2.astype(new_R2c.dtype)
            else:
                mean_c = _gather_mean(v2, i2, outer_axis, n_pods,
                                      view.d_row_total, jnp.float32)
            nnz_local += codec.nnz(i2).astype(jnp.float32)
        elif mc > 0.0:
            new_R2c = new_Vc
        else:
            new_R2c = R2s[c]
        means.append(mean_c)
        new_E_blocks.append(new_Ec)
        new_R2_blocks.append(new_R2c)

    # materialize the joined mean before unpacking: without the barrier
    # XLA fuses the concatenate into downstream consumers (e.g. the
    # optimizer's mul+add), where FMA contraction rounds differently
    # than the unchunked program — a 1-ULP drift that breaks the
    # bit-identity contract.  The unchunked path materializes its mean
    # at the wire collective, so this only restores parity.
    mean = jax.lax.optimization_barrier(jnp.concatenate(means, axis=1))
    new_E = jnp.concatenate([blk.astype(E.dtype) for blk in new_E_blocks],
                            axis=1)
    agg = unpack_tree(layout, mean, like=grads)
    bits_dense = float(sum(2 * g.size * jnp.dtype(g.dtype).itemsize * 8
                           for g in g_leaves))
    metrics = {
        **_replicated_outcomes(nnz_local, layout.d_total, at_cap, under,
                               axes),
        "density_cap": jnp.float32(
            M * layout.k_cap_total / layout.d_total),
        "comm_bits_sparse": jnp.float32(
            layout.comm_bits_sparse(strategy, world, n_pods, codec_dtype)),
        "comm_bits_dense": jnp.float32(bits_dense),
        "wire_bytes": jnp.float32(
            layout.comm_bits_sparse(strategy, world, n_pods,
                                    codec_dtype) / 8.0),
        # the ONE metric the chunked schedule changes: same wire volume,
        # N collectives per level instead of 1
        "collectives_per_step": jnp.float32(
            plan.collectives(strategy, world, n_pods)),
    }
    if adaptive:
        metrics["k_total"] = K_eff.astype(jnp.float32)
    new_resid2 = (jnp.concatenate(
        [blk.astype(R2.dtype) for blk in new_R2_blocks], axis=1
        ).reshape(-1) if resid2 is not None else None)
    return AggregateResult(agg, new_E.reshape(-1), new_resid2, new_adapt,
                           metrics)
