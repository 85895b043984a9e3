"""Pallas kernel: pass A of the fused EF pipeline (Mosaic + Triton).

Streams ``g`` (and optionally ``e``) block-wise, forms ``u = g + e`` in
registers and accumulates every statistic the threshold stage needs —
sum, sum-of-squares, abs-max and (optionally) the hist-k magnitude
histogram — WITHOUT writing ``u`` back to HBM.  This fuses the unfused
pipeline's ``u = g + e`` materialization pass with the ``moments`` (and
``abs_histogram``) passes into a single read of the operands.

Operands arrive as ``(nblocks, block)`` and are viewed as ``(rows, 128)``
(a free row-major reshape), so one block is a ``(block // 128, 128)``
tile: TPU-legal for ``block`` a multiple of 1024 (f32) / 2048 (bf16).
Each block is first folded, sub-tile by sub-tile, into an ``(8, 128)``
partial (``tuning.fold_tiles`` — elementwise adds only), and the partial
is then combined into the running statistics.  A sequential grid step
takes up to ``GROUP`` blocks and combines them one by one in block
order, so the addition sequence does not depend on the grouping.

Two lowerings share that per-block math (DESIGN.md §15):

* ``mosaic``/``interpret`` — the TPU shape: the grid is SEQUENTIAL, so
  one revisited accumulator (``(3·8, 128)`` f32 partial sums/maxes, and
  ``(BINS·8, 128)`` i32 histogram counts) carries the running
  statistics across grid steps;
* ``triton`` — GPU grid programs are PARALLEL CTAs, so a revisited
  accumulator would race.  Each program writes its ``(8, 128)`` partials
  to its OWN output rows instead, and the host combines them with an
  in-order left fold — ``((0 + p_0) + p_1) + …`` — which is exactly the
  float addition sequence the sequential grid performs, so the result
  is bit-equal to the Mosaic path at the same block size.  (max is
  associative; histogram adds are exact integer-valued f32 counts.)

Both end in the same reduction of the ``(8, 128)`` tiles to scalars.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ef_fused.tuning import (GROUP, LANES, acc_rows,
                                           block_rows, compiler_params,
                                           fold_tiles)
from repro.kernels.histk.hist import BINS, _bin_of


def _block_partials(x: jax.Array, sub: int):
    """(sum, sumsq, absmax) of one ``(rows, 128)`` block, each an
    ``(sub, 128)`` tile of partials."""
    return (fold_tiles(x, sub, jnp.add), fold_tiles(x * x, sub, jnp.add),
            fold_tiles(jnp.abs(x), sub, jnp.maximum))


def _load_u(refs, has_e: bool):
    if has_e:
        g_ref, e_ref = refs[0], refs[1]
        out = refs[2:]
        x = g_ref[...].astype(jnp.float32) + e_ref[...].astype(jnp.float32)
    else:
        g_ref, out = refs[0], refs[1:]
        x = g_ref[...].astype(jnp.float32)
    return x, out


def _kernel(*refs, has_e: bool, with_hist: bool, sub: int, rows: int):
    """Sequential-grid lowering: revisited accumulator tiles, combined
    block by block in block order."""
    x, out = _load_u(refs, has_e)
    acc_ref = out[0]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        for r in out:
            r[...] = jnp.zeros_like(r)

    acc_s, acc_sq = acc_ref[0:sub], acc_ref[sub:2 * sub]
    acc_mx = acc_ref[2 * sub:]
    for j in range(0, x.shape[0], rows):
        s, sq, mx = _block_partials(x[j:j + rows], sub)
        acc_s, acc_sq = acc_s + s, acc_sq + sq
        acc_mx = jnp.maximum(acc_mx, mx)
    acc_ref[0:sub] = acc_s
    acc_ref[sub:2 * sub] = acc_sq
    acc_ref[2 * sub:] = acc_mx
    if with_hist:
        hist_ref = out[1]
        bins = _bin_of(jnp.abs(x))

        def add_bin(b, carry):
            rows = pl.ds(pl.multiple_of(b * sub, sub), sub)
            hist_ref[rows] = hist_ref[rows] + fold_tiles(
                (bins == b).astype(jnp.int32), sub, jnp.add)
            return carry

        jax.lax.fori_loop(0, BINS, add_bin, 0)


def _partials_kernel(*refs, has_e: bool, with_hist: bool, sub: int):
    """Parallel-grid (Triton) lowering: each program owns its output rows."""
    x, out = _load_u(refs, has_e)
    s, sq, mx = _block_partials(x, sub)
    out[0][...] = jnp.concatenate([s, sq, mx])
    if with_hist:
        b = _bin_of(jnp.abs(x)).reshape(-1)
        bins = jax.lax.broadcasted_iota(jnp.int32, (BINS, b.shape[0]), 0)
        oh = (bins == b[None, :]).astype(jnp.int32)
        out[1][0, :] = jnp.sum(oh, axis=1)


def _combine_partials(parts: jax.Array, nblocks: int, sub: int):
    """Host-side fold of the per-block partial tiles: s/sq strictly
    left-to-right in block order — the exact addition sequence of the
    sequential grid; max is order-free."""
    parts = parts.reshape(nblocks, 3 * sub, LANES)

    def body(i, acc):
        p = parts[i]
        return jnp.concatenate([acc[:2 * sub] + p[:2 * sub],
                                jnp.maximum(acc[2 * sub:], p[2 * sub:])])

    return jax.lax.fori_loop(0, nblocks, body,
                             jnp.zeros((3 * sub, LANES), jnp.float32))


@functools.partial(jax.jit, static_argnames=("block", "with_hist", "backend",
                                             "num_warps", "num_stages",
                                             "interpret"))
def fused_moments(g2d: jax.Array, e2d: jax.Array | None = None, *,
                  block: int = 2048, with_hist: bool = False,
                  backend: str = "interpret", num_warps: int = 4,
                  num_stages: int = 2, interpret: bool = True):
    """(sum, sumsq, absmax[, hist]) of ``u = g + e`` over (nblocks, block)
    operands — one HBM pass, ``u`` never materialized.

    ``backend`` picks the kernel SHAPE (sequential accumulator vs
    parallel partials); ``interpret`` picks the EXECUTION engine —
    ``backend="triton", interpret=True`` runs the GPU lowering under the
    Pallas interpreter (the CPU CI smoke path).
    """
    nblocks, b = g2d.shape
    assert b == block, (g2d.shape, block)
    rows = block_rows(block)
    sub = acc_rows(rows)
    has_e = e2d is not None
    operands = tuple(x.reshape(-1, LANES)
                     for x in ((g2d, e2d) if has_e else (g2d,)))
    parallel = backend == "triton"
    group = 1 if parallel else math.gcd(nblocks, GROUP)
    data_spec = pl.BlockSpec((group * rows, LANES), lambda i: (i, 0))
    if parallel:
        out_specs = [pl.BlockSpec((3 * sub, LANES), lambda i: (i, 0))]
        out_shape = [jax.ShapeDtypeStruct((nblocks * 3 * sub, LANES),
                                          jnp.float32)]
        if with_hist:
            out_specs.append(pl.BlockSpec((1, BINS), lambda i: (i, 0)))
            out_shape.append(jax.ShapeDtypeStruct((nblocks, BINS),
                                                  jnp.int32))
    else:
        out_specs = [pl.BlockSpec((3 * sub, LANES), lambda i: (0, 0))]
        out_shape = [jax.ShapeDtypeStruct((3 * sub, LANES), jnp.float32)]
        if with_hist:
            out_specs.append(pl.BlockSpec((BINS * sub, LANES),
                                          lambda i: (0, 0)))
            out_shape.append(jax.ShapeDtypeStruct((BINS * sub, LANES),
                                                  jnp.int32))
    kern = (functools.partial(_partials_kernel, has_e=has_e,
                              with_hist=with_hist, sub=sub) if parallel
            else functools.partial(_kernel, has_e=has_e, with_hist=with_hist,
                                   sub=sub, rows=rows))
    outs = pl.pallas_call(
        kern,
        name="fused_moments",
        grid=(nblocks // group,),
        in_specs=[data_spec] * len(operands),
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=compiler_params(backend, num_warps, num_stages),
    )(*operands)
    if parallel:
        acc = _combine_partials(outs[0], nblocks, sub)
        h = jnp.sum(outs[1], axis=0) if with_hist else None
    else:
        acc = outs[0]
        h = (jnp.sum(outs[1].reshape(BINS, -1), axis=1) if with_hist
             else None)
    s = jnp.sum(acc[:sub])
    sq = jnp.sum(acc[sub:2 * sub])
    mx = jnp.max(acc[2 * sub:])
    return s, sq, mx, None if h is None else h.astype(jnp.float32)
