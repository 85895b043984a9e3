"""Fused error-feedback compression pipelines (DESIGN.md §8, §15).

``fused_compress_ef`` is the ~3-pass pipeline; ``unfused_compress_ef``
composes the SAME kernels the pre-fusion way (materialize ``u``, moments
pass, sequential count refinement, compact, dense decode, residual
subtract — ~8 passes) and is the apples-to-apples baseline for
``benchmarks/fig4_selection_speed.py`` as well as the bit-exactness
oracle: both pipelines share every per-block op and the staging
assembly, so for f32 operands their outputs are identical bit-for-bit.

Every entry point lowers through one of three kernel backends
(``tuning.resolve_backend``): ``mosaic`` (TPU), ``triton`` (GPU — the
parallel-grid kernel shapes, one extra residual pass) or ``interpret``
(the Pallas interpreter).  ``backend=None`` picks the compiled lowering
for the running platform; the legacy ``interpret=`` bool still works
behind one DeprecationWarning.  Block configuration comes from
``tuning.resolve_config`` (checked-in per-platform table → in-process
cache → measured autotune on compiled backends → deterministic
heuristic).

Both entry points are plain Python compositions of jitted kernels — NOT
jitted at this level — so the :mod:`passes` accounting runs on every
call (wrap in ``jax.jit`` at the call site for dispatch-free timing).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.scipy.stats import norm

from repro.core import codec
from repro.kernels.ef_fused import passes, tuning
from repro.kernels.ef_fused.compact_residual import compact_residual
from repro.kernels.ef_fused.fused_moments import fused_moments
from repro.kernels.ef_fused.tree_count import tree_count
from repro.kernels.ef_fused.tuning import (MAX_INTERPRET_BLOCKS,
                                           MAX_INTERPRET_STATS_BLOCKS)
from repro.kernels.gaussian_topk.ops import (assemble_staging, default_bcap,
                                             gaussian_threshold_kernel,
                                             select_by_threshold)
from repro.kernels.histk.ops import (histk_cap, histk_threshold,
                                     threshold_from_histogram)

# compressor names whose selection rule the fused pipeline implements:
# threshold-from-statistics + fixed-capacity compaction, key-free
FUSED_COMPRESSORS = ("gaussiank", "gaussiank2", "histk")

MIN_BLOCK = tuning.INTERPRET_MIN_BLOCK          # legacy alias


def supports_fused(name: str) -> bool:
    return name in FUSED_COMPRESSORS


def choose_block(d: int, interpret: bool = True, *,
                 backend: str | None = None, dtype="float32") -> int:
    """Compaction-kernel block size (legacy shim over tuning.choose_block).

    Interpret-mode grids pay O(d) buffer materialization per grid step,
    so the interpreter bounds the block COUNT; compiled backends take
    the per-(backend, dtype) tile minimum — see ``tuning.min_block``.
    """
    if backend is None:
        backend = "interpret" if interpret else "mosaic"
    return tuning.choose_block(d, backend, dtype)


def choose_stats_block(d: int, interpret: bool = True, *,
                       backend: str | None = None, dtype="float32") -> int:
    """Block size for the reduction kernels (moments/hist/counts) — these
    have O(1)-per-element compute and tiny outputs, so under the
    interpreter they want the largest blocks possible."""
    if backend is None:
        backend = "interpret" if interpret else "mosaic"
    return tuning.choose_stats_block(d, backend, dtype)


def fused_default_bcap(k_cap: int, d: int, block: int,
                       slack: float = 2.0) -> int:
    """Per-block staging width of the fused compaction: ``slack``× the
    expected per-block selection (default 2x, vs the unfused default's
    4x).  The staging matmul costs O(bcap · block) per block, so the
    tighter slack halves the dominant compaction cost; a >2x per-block
    fluctuation only truncates the staging, and the dropped mass stays
    in the residual by the on-wire accounting (one step of staleness,
    never lost)."""
    expected = k_cap * block / max(d, 1)
    return int(min(block, max(64, 8 * math.ceil(expected * slack / 8))))


def _pad2d(x: jax.Array, block: int):
    d = x.shape[0]
    pad = (-d) % block
    return jnp.pad(x, (0, pad)).reshape(-1, block), pad


def _tree_thresholds(t0: jax.Array, refine_iters: int):
    """Heap-ordered thresholds of the refinement tree, depth 0..R.

    ``heap[2i+1] = 0.5·heap[i]`` (count below band → lower threshold),
    ``heap[2i+2] = 1.5·heap[i]`` — the exact float products the
    sequential loop would compute along any visit path.  Counts are only
    needed at internal nodes (depth < R, the first ``2^R − 1`` entries);
    the final threshold can land on a leaf.
    """
    n_full = 2 ** (refine_iters + 1) - 1
    heap = [t0] + [None] * (n_full - 1)
    for i in range((n_full - 1) // 2):
        heap[2 * i + 1] = 0.5 * heap[i]
        heap[2 * i + 2] = 1.5 * heap[i]
    return jnp.stack(heap), 2 ** refine_iters - 1


def _replay_refinement(heap: jax.Array, counts: jax.Array, k: int,
                       refine_iters: int) -> jax.Array:
    """Replay Algorithm 1's refinement decisions on the count table.

    Identical decision rule to ``gaussian_threshold_kernel``'s loop: the
    walk moves to the half/1.5× child while the count is out of the
    accept band and freezes once inside it.
    """
    lo = 2.0 * k / 3.0
    hi = 4.0 * k / 3.0

    def body(_, carry):
        idx, done = carry
        est = counts[idx].astype(jnp.float32)
        in_band = (est >= lo) & (est <= hi)
        nxt = jnp.where(est < lo, 2 * idx + 1, 2 * idx + 2)
        idx = jnp.where(done | in_band, idx, nxt)
        return idx, done | in_band

    idx, _ = jax.lax.fori_loop(0, refine_iters, body,
                               (jnp.int32(0), jnp.bool_(False)))
    return heap[idx]


@jax.named_scope("ef.select")
def _gaussian_threshold_fused(g2d, e2d, d: int, k, *, block: int,
                              refine_iters: int, two_sided: bool,
                              kcfg: "tuning.KernelConfig",
                              interpret: bool, moments=None) -> jax.Array:
    if moments is None:
        s, sq, _, _ = fused_moments(g2d, e2d, block=block,
                                    backend=kcfg.backend,
                                    num_warps=kcfg.num_warps,
                                    num_stages=kcfg.num_stages,
                                    interpret=interpret)
        passes.record("moments", 1)
    else:
        s, sq = moments
    mean = s / d
    var = jnp.maximum(sq / d - mean * mean, 0.0)
    std = jnp.sqrt(var)
    p = 1.0 - (k / (2.0 * d) if two_sided else k / d)
    t0 = jnp.maximum(jnp.abs(norm.ppf(p, mean, std + 1e-12)), 0.0)
    heap, n_cnt = _tree_thresholds(t0, refine_iters)
    counts = tree_count(g2d, e2d, heap[:n_cnt], n_t=n_cnt, block=block,
                        backend=kcfg.backend, num_warps=kcfg.num_warps,
                        num_stages=kcfg.num_stages, interpret=interpret)
    passes.record("tree_count", 1)
    return _replay_refinement(heap, counts, k, refine_iters)


@jax.named_scope("ef.select")
def _hist_threshold_fused(g2d, e2d, d: int, k, pad: int, *, block: int,
                          kcfg: "tuning.KernelConfig",
                          interpret: bool, hist=None) -> jax.Array:
    # identical post-processing to histk_threshold (shared helper) on
    # the fused histogram
    if hist is None:
        _, _, _, hist = fused_moments(g2d, e2d, block=block, with_hist=True,
                                      backend=kcfg.backend,
                                      num_warps=kcfg.num_warps,
                                      num_stages=kcfg.num_stages,
                                      interpret=interpret)
        passes.record("moments+hist", 1)
    return threshold_from_histogram(hist, k, pad)


def _resolve(g, e, name, k, k_cap, block, stats_block, bcap, interpret,
             backend=None, bcap_default=default_bcap):
    """Three-way backend + KernelConfig resolution (DESIGN.md §15).

    Explicit ``block``/``stats_block``/``bcap`` kwargs always win; the
    remaining holes are filled from ``tuning.resolve_config`` — the
    checked-in per-platform table first, then the autotune cache, then
    a measured autotune (compiled backends) or the deterministic
    heuristic (interpreter).  Returns ``(d, k_cap, block, stats_block,
    bcap, cfg)`` where ``cfg`` carries the backend name and the Triton
    ``num_warps``/``num_stages``.
    """
    if not supports_fused(name):
        raise ValueError(f"compressor {name!r} has no fused pipeline; "
                         f"supported: {FUSED_COMPRESSORS}")
    backend = tuning.resolve_backend(backend, interpret)
    d = g.shape[0]
    if e is not None:
        assert e.shape == g.shape, (g.shape, e.shape)
    if block is None or stats_block is None:
        cfg = tuning.resolve_config(d, g.dtype, backend=backend)
    else:
        cfg = tuning.KernelConfig(backend=backend, block=block,
                                  stats_block=stats_block, source="explicit")
    if block is None:
        block = cfg.block
    if stats_block is None:
        stats_block = cfg.stats_block
    if k_cap is None:
        k_cap = histk_cap(k, d)      # == gaussiank_cap (4k/3 band edge)
    if bcap is None:
        if bcap_default is fused_default_bcap:
            bcap = bcap_default(k_cap, d, block, cfg.bcap_slack)
        else:
            bcap = bcap_default(k_cap, d, block)
    return d, k_cap, block, stats_block, bcap, cfg


@jax.named_scope("ef.select")
def fused_pass_a(g: jax.Array, e: jax.Array | None, name: str, *,
                 stats_block: int | None = None,
                 interpret: bool | None = None,
                 backend: str | None = None,
                 fuse_operands: bool | None = None):
    """Pass A of the fused pipeline, standalone: the ``(sum, sumsq,
    absmax, hist)`` statistics of ``u = g + e`` (``hist`` is ``None``
    except for ``histk``), computed with the exact block/fusion policy
    ``fused_compress_ef`` would use for the same operands — hand the
    result back via its ``stats=`` argument and the pipeline's own
    moments pass is skipped, bit-identically.

    This is the adaptive-density hook (DESIGN.md §9): a controller reads
    every leaf's moments first, redistributes the global budget into
    per-leaf traced ``k``'s, then runs threshold+compaction — pass A is
    still executed exactly once per leaf.  Only the moments/hist read is
    counted in :mod:`passes` here; the ``u`` materialization the
    unfused-operand (interpreter) shape performs is charged by the
    compress call, which re-forms it (XLA CSEs the duplicate add).
    """
    if not supports_fused(name):
        raise ValueError(f"compressor {name!r} has no fused pipeline; "
                         f"supported: {FUSED_COMPRESSORS}")
    backend = tuning.resolve_backend(backend, interpret)
    interp = tuning.exec_interpret(backend)
    d = g.shape[0]
    if e is not None:
        assert e.shape == g.shape, (g.shape, e.shape)
    cfg = tuning.resolve_config(d, g.dtype, backend=backend)
    if stats_block is None:
        stats_block = cfg.stats_block
    if fuse_operands is None:
        fuse_operands = backend != "interpret"
    if e is not None and not fuse_operands:
        a, b = g.astype(jnp.result_type(g.dtype, e.dtype)) + e, None
    else:
        a, b = g, e
    a_s, _ = _pad2d(a, stats_block)
    b_s = _pad2d(b, stats_block)[0] if b is not None else None
    with_hist = name == "histk"
    s, sq, mx, h = fused_moments(a_s, b_s, block=stats_block,
                                 with_hist=with_hist, backend=backend,
                                 num_warps=cfg.num_warps,
                                 num_stages=cfg.num_stages,
                                 interpret=interp)
    passes.record("moments+hist" if with_hist else "moments", 1)
    return s, sq, mx, h


def fused_compress_ef(g: jax.Array, e: jax.Array | None, name: str, k,
                      *, k_cap: int | None = None, block: int | None = None,
                      stats_block: int | None = None, refine_iters: int = 4,
                      bcap: int | None = None,
                      interpret: bool | None = None,
                      backend: str | None = None,
                      num_warps: int | None = None,
                      num_stages: int | None = None,
                      fuse_operands: bool | None = None,
                      write_resid: bool | None = None,
                      stats=None):
    """One EF compression step on ``u = g + e``, fused (DESIGN.md §8).

    Returns ``(values, indices, new_e)`` with the Eq. (2) conservation
    invariant ``decode(values, indices, d) + new_e == g + e`` holding
    bit-for-bit (selected coordinates are zeroed in ``new_e``;
    everything else — including staging/capacity overflow — keeps its
    ``u`` value).  ``e=None`` treats ``g`` as the already-accumulated
    vector.  Output dtypes follow the f32-promoted accumulation
    (``new_e`` in the promoted dtype), matching ``compress_with_ef``'s
    reference arithmetic when the residual is f32.

    ``backend`` selects the kernel lowering (``tuning.BACKENDS``;
    ``None`` = the platform's compiled lowering, overridable via
    ``tuning.use_backend`` / ``REPRO_KERNEL_BACKEND``).  The legacy
    ``interpret=`` bool is a deprecation shim over the same resolution.

    ``fuse_operands`` streams ``g`` and ``e`` into the kernels unsummed
    (no materialized ``u``) and ``write_resid`` writes ``e'`` inside the
    compaction sweep — the 3-pass shape that is right on a real TPU,
    where every materialization is an HBM round-trip (on Triton the
    residual write is its own race-free pass: 4 total).  Under the
    ``interpret`` backend both fusions are counterproductive — the
    interpreter charges O(d) per grid step per operand/carried output,
    while an XLA elementwise add or k-sized scatter is one cheap fused
    op — so it defaults both off: ``u`` is materialized once, the
    kernels run single-operand, and the residual is rebuilt as
    ``u.at[wire_indices].set(0)`` (bit-equal: wire values are exact
    ``u`` elements).

    ``stats`` accepts a precomputed pass-A tuple from
    :func:`fused_pass_a` (same operands, same block config) and skips
    the internal moments/hist pass.  ``k`` may then be a *traced* scalar
    (adaptive density, DESIGN.md §9) as long as every shape-bearing
    argument — ``k_cap`` in particular — is passed statically: ``k``
    only enters the threshold math and the refinement accept band.
    """
    d, k_cap, block, stats_block, bcap, cfg = _resolve(
        g, e, name, k, k_cap, block, stats_block, bcap, interpret,
        backend=backend, bcap_default=fused_default_bcap)
    if num_warps is not None or num_stages is not None:
        cfg = dataclasses.replace(
            cfg,
            num_warps=cfg.num_warps if num_warps is None else num_warps,
            num_stages=cfg.num_stages if num_stages is None else num_stages)
    kbackend = cfg.backend
    interp = tuning.exec_interpret(kbackend)
    if fuse_operands is None:
        fuse_operands = kbackend != "interpret"
    if write_resid is None:
        write_resid = kbackend != "interpret"
    out_dtype = jnp.result_type(g.dtype, e.dtype) if e is not None else g.dtype

    if e is not None and not fuse_operands:
        a, b = g.astype(out_dtype) + e, None
        passes.record("residual_add", 1)
    else:
        a, b = g, e
    a_s, pad_s = _pad2d(a, stats_block)
    b_s = _pad2d(b, stats_block)[0] if b is not None else None
    if name == "histk":
        thres = _hist_threshold_fused(a_s, b_s, d, k, pad_s,
                                      block=stats_block, kcfg=cfg,
                                      interpret=interp,
                                      hist=None if stats is None
                                      else stats[3])
    else:
        thres = _gaussian_threshold_fused(
            a_s, b_s, d, k, block=stats_block, refine_iters=refine_iters,
            two_sided=(name == "gaussiank2"), kcfg=cfg, interpret=interp,
            moments=None if stats is None else stats[:2])
    thres = jnp.maximum(jnp.asarray(thres, jnp.float32), 0.0)

    # pass B (and its residual write) is the compaction's scope
    with jax.named_scope("ef.compact"):
        a_c = _pad2d(a, block)[0]
        b_c = _pad2d(b, block)[0] if b is not None else None
        vals, offs, cnts, newe = compact_residual(
            a_c, b_c, thres, bcap=bcap, k_cap=k_cap, block=block,
            out_dtype=jnp.dtype(out_dtype).name, with_resid=write_resid,
            backend=kbackend, num_warps=cfg.num_warps,
            num_stages=cfg.num_stages, interpret=interp)
        if write_resid and kbackend == "triton":
            # the Triton lowering splits compaction and residual into two
            # race-free sweeps (see compact_residual) — charge both
            passes.record("compact", 1)
            passes.record("residual_write", 1)
        else:
            passes.record("compact+residual" if write_resid else "compact",
                          1)
        values, indices = assemble_staging(vals, offs, cnts, d, k_cap,
                                           block=block, out_dtype=out_dtype)
        if write_resid:
            new_e = newe.reshape(-1)[:d]
        else:
            # wire values are exact u elements, so zeroing them IS
            # u − decode
            u = a if b is None else a + b
            safe = jnp.where(indices == codec.SENTINEL, d, indices)
            new_e = u.at[safe].set(0.0, mode="drop")
            passes.record("residual_scatter", 1)
    return values, indices, new_e


def unfused_compress_ef(g: jax.Array, e: jax.Array | None, name: str, k: int,
                        *, k_cap: int | None = None, block: int | None = None,
                        stats_block: int | None = None,
                        refine_iters: int = 4, bcap: int | None = None,
                        interpret: bool | None = None,
                        backend: str | None = None):
    """The pre-fusion pipeline over the same kernels (perf baseline/oracle).

    Materializes ``u = g + e``, runs the unfused threshold kernels
    (moments + sequential ``count_gt`` refinement, or the histogram
    pass), block-compacts, then pays the dense ``decode`` and the
    ``u − decode`` subtract for the residual — the ~8-9 leaf-sized HBM
    passes the fused pipeline collapses to ~3.  Uses the same per-pass
    block policy as the fused pipeline; each pipeline keeps its own
    staging default though (``default_bcap`` 4x vs ``fused_default_bcap``
    2x — the tighter slack is part of the fused design, enabled by its
    exact on-wire residual accounting), so the fig4 comparison measures
    the two pipelines as shipped: pass structure AND staging width.
    Pass ``bcap`` explicitly to both for a staging-equalized run.

    The legacy kernels only have the sequential-grid lowering, so any
    backend other than ``mosaic``-on-TPU executes them under the
    interpreter (they would race on a parallel GPU grid).
    """
    d, k_cap, block, stats_block, bcap, cfg = _resolve(
        g, e, name, k, k_cap, block, stats_block, bcap, interpret,
        backend=backend)
    legacy_interpret = (cfg.backend != "mosaic"
                       or tuning.exec_interpret(cfg.backend))
    if e is not None:
        u = g.astype(jnp.result_type(g.dtype, e.dtype)) + e
        passes.record("residual_add", 1)
    else:
        u = g
    if name == "histk":
        thres = histk_threshold(u, k, block=stats_block,
                                interpret=legacy_interpret)
        passes.record("hist", 1)
    else:
        thres = gaussian_threshold_kernel(
            u, k, block=stats_block, refine_iters=refine_iters,
            two_sided=(name == "gaussiank2"), interpret=legacy_interpret)
        passes.record("moments", 1)
        # the fori_loop body traces once but streams u every iteration
        passes.record("count_gt", refine_iters)
    values, indices = select_by_threshold(u, thres, k_cap, block=block,
                                          bcap=bcap,
                                          interpret=legacy_interpret)
    passes.record("compact", 1)
    dec = codec.decode(values.astype(u.dtype), indices, d)
    passes.record("dense_decode", 1)
    new_e = u - dec
    passes.record("residual_subtract", 1)
    return values, indices, new_e
