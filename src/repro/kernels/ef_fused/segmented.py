"""Segment-aware fused-pipeline ops over a packed bucket grid (DESIGN.md
§10).

The bucketed aggregation path (``dist/layout.py``) packs every gradient
leaf's ``(model_size, d_row)`` rows into one contiguous
``(model_size, d_row_total)`` bucket.  The ops here run the fused EF
pipeline (§8) over that bucket per static column segment:

* each segment keeps its OWN block configuration (``tuning.
  resolve_config`` of its ``d_row``), so every per-row kernel call
  is bit-identical to the per-leaf pipeline on the same values — the
  bucketing collapses *wire messages*, never numerics;
* what the caller gets back is already bucket-shaped: one residual
  bucket write per step instead of L per-leaf pad/reshape round-trips.

``rows_pass_a`` / ``rows_compress_ef`` are the shared row-block
primitives (one leaf's ``(model_size, d_row)`` rows) used by BOTH the
per-leaf path (``dist/aggregate.py``) and the segmented entry points —
single source of truth for the bit-equality contract.

Every entry point takes an optional kernel ``backend``
(mosaic/triton/interpret, default: the platform resolution of
``tuning.resolve_backend`` — which honors ``tuning.use_backend`` /
``REPRO_KERNEL_BACKEND``, so callers that do not thread kernel kwargs,
like ``dist/aggregate``, are still covered by a process-wide override).
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ef_fused.ops import fused_compress_ef, fused_pass_a
from repro.spmd import manual_over_auto_axes


def rows_pass_a(g_rows: jax.Array, e_rows: jax.Array, name: str,
                backend: Optional[str] = None) -> list:
    """Per-row pass-A statistic tuples of ``u = g + e`` for one
    ``(model_size, d_row)`` row block — each row with the exact
    block/fusion policy ``fused_compress_ef`` would choose for it, so the
    tuples can be handed back via its ``stats=`` argument bit-identically.
    """
    def rows(g_rows, e_rows):
        return [fused_pass_a(g_rows[r], e_rows[r], name, backend=backend)
                for r in range(g_rows.shape[0])]

    return manual_over_auto_axes(rows, g_rows, e_rows)


def rows_compress_ef(g_rows: jax.Array, e_rows: jax.Array, name: str, k, *,
                     k_cap: int, row_stats=None,
                     backend: Optional[str] = None):
    """Fused EF compression of one ``(model_size, d_row)`` row block.

    One fused pipeline per model-shard row — ``u = e + g`` accumulates
    inside the kernels and the new residual is written by the compaction
    pass (DESIGN.md §8).  ``k`` may be a traced scalar when ``row_stats``
    (per-row :func:`rows_pass_a` tuples) is supplied (adaptive density,
    §9).  Returns ``(values, indices, new_e_rows)`` with static shapes
    ``(model_size, k_cap)`` / ``(model_size, d_row)``.
    """
    def rows(g_rows, e_rows, k, row_stats):
        outs = [fused_compress_ef(g_rows[r], e_rows[r], name, k,
                                  k_cap=k_cap, backend=backend,
                                  stats=None if row_stats is None
                                  else row_stats[r])
                for r in range(g_rows.shape[0])]
        return tuple(jnp.stack([o[i] for o in outs]) for i in range(3))

    return manual_over_auto_axes(rows, g_rows, e_rows, k, row_stats)


def segmented_pass_a(g2d: jax.Array, e2d: jax.Array,
                     segments: Sequence[Tuple[int, int]],
                     name: str,
                     backend: Optional[str] = None) -> List[list]:
    """Pass A over the packed bucket: per ``(start, length)`` column
    segment, the per-row pass-A tuples of that segment's rows —
    bit-identical to running :func:`rows_pass_a` leaf-at-a-time (each
    segment keeps its own ``d_row``-derived block config)."""
    return [rows_pass_a(g2d[:, start:start + length],
                        e2d[:, start:start + length], name, backend=backend)
            for start, length in segments]


def segmented_compress_ef(g2d: jax.Array, e2d: jax.Array,
                          segments: Sequence[Tuple[int, int]], name: str,
                          ks: Sequence, k_caps: Sequence[int], *,
                          stats: Optional[Sequence] = None,
                          backend: Optional[str] = None):
    """Fused threshold-compact + residual write over the bucket grid.

    Per ``(start, length)`` segment: run :func:`rows_compress_ef` on the
    segment's rows with its own budget ``ks[i]`` (static or traced) and
    static capacity ``k_caps[i]``; ``stats[i]`` optionally carries the
    segment's :func:`segmented_pass_a` tuples.  Returns the per-segment
    ``(values, indices, new_e_rows)`` triples in segment order — the
    caller concatenates them into the single wire block / residual
    bucket (``dist/aggregate.aggregate_bucketed``).
    """
    out = []
    for i, (start, length) in enumerate(segments):
        out.append(rows_compress_ef(
            g2d[:, start:start + length], e2d[:, start:start + length],
            name, ks[i], k_cap=k_caps[i], backend=backend,
            row_stats=None if stats is None else stats[i]))
    return out
