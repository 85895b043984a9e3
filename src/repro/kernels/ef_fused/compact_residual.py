"""Pallas kernel: pass B of the fused EF pipeline — threshold-compact
AND residual write in one sweep.

The unfused pipeline pays three leaf-sized passes after selection: the
block compaction, a dense ``decode`` of the selected pairs, and the
``e' = u − decode`` subtract.  But the residual is known block-locally
at compaction time: every element is either on the wire (residual 0) or
it is not (residual ``u``).  This kernel streams ``g`` (+ optional
``e``), forms ``u`` in registers, stages the compacted values/offsets
in the ``threshold_compact`` staging layout (so the downstream assembly
is shared) and writes ``e'`` in the same sweep.

Sequential (mosaic) shape: one grid step takes ``GROUP`` (8) consecutive
blocks as one ``(GROUP · block // 128, 128)`` tile and writes their
``GROUP`` staging rows as one ``(GROUP, bcap)`` tile (fewer than 8
blocks go in one step whose tile spans the whole array).  Within a block,
the selected elements are staged in index order by extraction: step
``j`` takes the smallest selected flat offset above the previous one
(an i32 min-reduction) and reads its value with a one-nonzero masked
sum.  Both are exact on every lowering — staged values are bit-copies
of ``u`` and offsets are integers — and the loop runs once per staged
element, not once per slot.

Global-capacity truncation: an element can be staged per-block yet still
dropped by the final ``k_cap`` assembly cut.  TPU grids are sequential,
so an SMEM scratch scalar carries the running number of staged slots in
preceding blocks (``enc_before``); with it the kernel knows how many of
this block's staged elements land below ``k_cap`` and keeps exactly the
wire-surviving elements out of ``e'`` — the dropped ones stay in the
residual, preserving Eq. (2) conservation bit-for-bit.

The ``triton`` lowering cannot carry ``enc_before`` across grid programs
(parallel CTAs), so it splits into TWO race-free passes: a staging
kernel that emits each block's ``(vals, offs, cnt)`` to its own rows,
then — after an exact i32 exclusive cumsum of the capped counts in XLA —
a residual kernel that re-streams the operands with each block's
``enc_before`` scalar and writes ``e'``.  One extra HBM pass on GPU
(4 total for Gaussian-k vs the TPU shape's 3), still far below the
~8-pass unfused baseline.  Its staging selects with a masked
select-and-sum (no ``tl.dot``, which may round f32 through tf32), and
the cumsum runs in i32 where addition is exact in any association, so
its output is bit-equal to the sequential lowering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.codec import SENTINEL
from repro.kernels.ef_fused.tuning import (GROUP, LANES, block_rows,
                                           compiler_params)

_NONE = jnp.iinfo(jnp.int32).max


def _extract(x, flat, mask, lo, hi, carry):
    """Stage selected elements ``lo..hi-1`` (in index order) into the
    ``(1, bcap)`` rows of ``carry = (vals, offs, prev)``; ``prev`` is the
    flat offset of the last element staged so far (-1 for none)."""
    slot = jax.lax.broadcasted_iota(jnp.int32, carry[0].shape, 1)

    def body(j, c):
        vals, offs, prev = c
        idx = jnp.min(jnp.where(mask & (flat > prev), flat, _NONE))
        v = jnp.sum(jnp.where(flat == idx, x, 0.0))
        at = slot == j
        return jnp.where(at, v, vals), jnp.where(at, idx, offs), idx

    return jax.lax.fori_loop(lo, hi, body, carry)


def _kernel(t_ref, *refs, has_e: bool, bcap: int, k_cap: int,
            with_resid: bool, rows: int, group: int):
    """Sequential-grid lowering: staging + residual in ONE sweep."""
    n_in = 2 if has_e else 1
    g_ref, e_ref = refs[0], refs[1] if has_e else None
    vals_ref, offs_ref, cnt_ref = refs[n_in:n_in + 3]
    newe_ref = refs[n_in + 3] if with_resid else None
    enc_ref = refs[-1]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        enc_ref[0] = 0

    thres = t_ref[0]
    u = g_ref[...].astype(jnp.float32)
    if e_ref is not None:
        u = u + e_ref[...].astype(jnp.float32)
    flat = (jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1))
    enc = enc_ref[0]                              # staged slots before us
    for s in range(group):
        x = u[s * rows:(s + 1) * rows]
        mask = jnp.abs(x) > thres
        cnt = jnp.sum(mask.astype(jnp.int32))
        n = jnp.minimum(cnt, bcap)
        # the first n_wire staged elements land below the k_cap cut
        n_wire = jnp.clip(k_cap - enc, 0, n)
        carry = (jnp.zeros((1, bcap), jnp.float32),
                 jnp.full((1, bcap), SENTINEL, jnp.int32), jnp.int32(-1))
        carry = _extract(x, flat, mask, 0, n_wire, carry)
        cut = carry[2]                            # last on-wire offset
        vals, offs, _ = _extract(x, flat, mask, n_wire, n, carry)
        vals_ref[s:s + 1] = vals
        offs_ref[s:s + 1] = offs
        cnt_ref[s:s + 1] = jnp.full((1, LANES), cnt, jnp.int32)
        if with_resid:
            on_wire = mask & (flat <= cut)
            newe_ref[s * rows:(s + 1) * rows] = jnp.where(
                on_wire, 0.0, x).astype(newe_ref.dtype)
        enc = enc + n
    enc_ref[0] = enc


def _block_select(x: jax.Array, thres, bcap: int):
    """Triton per-block selection: (mask, pos, keep, cnt)."""
    mask = jnp.abs(x) > thres
    cnt = jnp.sum(mask.astype(jnp.int32))
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
    keep = mask & (pos < bcap)                    # staged in this block
    return mask, pos, keep, cnt


def _stage(x: jax.Array, pos, keep, cnt, bcap: int):
    """Compact the kept elements into the (bcap,) staging rows with a
    masked select-and-sum: each staging row has at most one nonzero term
    and float adds with ±0.0 are exact, so staging is a bit-copy."""
    b = x.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (bcap, b), 0)
    sel = (rows == pos[None, :]) & keep[None, :]
    vals = jnp.sum(jnp.where(sel, x[None, :], 0.0), axis=1)
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)
    offs_i = jnp.sum(jnp.where(sel, iota, 0), axis=1)
    got = jnp.arange(bcap, dtype=jnp.int32) < jnp.minimum(cnt, bcap)
    return vals, jnp.where(got, offs_i, SENTINEL)


def _load_row(t_ref, g_ref, e_ref):
    x = g_ref[0, :].astype(jnp.float32)
    if e_ref is not None:
        x = x + e_ref[0, :].astype(jnp.float32)
    return x, t_ref[0, 0]


def _stage_kernel(*refs, has_e: bool, bcap: int):
    """Triton pass 1: per-block staging rows, no cross-program state."""
    if has_e:
        t_ref, g_ref, e_ref, vals_ref, offs_ref, cnt_ref = refs
    else:
        (t_ref, g_ref, vals_ref, offs_ref, cnt_ref), e_ref = refs, None
    x, thres = _load_row(t_ref, g_ref, e_ref)
    _, pos, keep, cnt = _block_select(x, thres, bcap)
    vals, offs = _stage(x, pos, keep, cnt, bcap)
    vals_ref[0, :] = vals
    offs_ref[0, :] = offs
    cnt_ref[0, :] = jnp.full((128,), cnt, jnp.int32)


def _resid_kernel(*refs, has_e: bool, bcap: int, k_cap: int):
    """Triton pass 2: residual write, given this block's ``enc_before``."""
    if has_e:
        t_ref, enc_ref, g_ref, e_ref, newe_ref = refs
    else:
        (t_ref, enc_ref, g_ref, newe_ref), e_ref = refs, None
    x, thres = _load_row(t_ref, g_ref, e_ref)
    _, pos, keep, _ = _block_select(x, thres, bcap)
    on_wire = keep & (enc_ref[0, 0] + pos < k_cap)
    newe_ref[0, :] = jnp.where(on_wire, 0.0, x).astype(newe_ref.dtype)


def _compact_residual_triton(operands, t, *, nblocks, block, bcap, k_cap,
                             out_dtype, with_resid, interpret, params):
    has_e = len(operands) == 2
    data_spec = pl.BlockSpec((1, block), lambda i: (i, 0))
    scalar_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    in_specs = [scalar_spec] + [data_spec] * len(operands)
    vals, offs, cnts = pl.pallas_call(
        functools.partial(_stage_kernel, has_e=has_e, bcap=bcap),
        name="compact_residual_stage",
        grid=(nblocks,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bcap), lambda i: (i, 0)),
            pl.BlockSpec((1, bcap), lambda i: (i, 0)),
            pl.BlockSpec((1, 128), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nblocks, bcap), jnp.float32),
            jax.ShapeDtypeStruct((nblocks, bcap), jnp.int32),
            jax.ShapeDtypeStruct((nblocks, 128), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=params,
    )(t, *operands)
    newe = None
    if with_resid:
        # exact i32 exclusive cumsum of the capped per-block counts
        capped = jnp.minimum(cnts[:, 0], bcap)
        enc_before = (jnp.cumsum(capped) - capped).reshape(-1, 1)
        resid_in_specs = ([scalar_spec,
                           pl.BlockSpec((1, 1), lambda i: (i, 0))]
                          + [data_spec] * len(operands))
        newe = pl.pallas_call(
            functools.partial(_resid_kernel, has_e=has_e, bcap=bcap,
                              k_cap=k_cap),
            name="compact_residual_resid",
            grid=(nblocks,),
            in_specs=resid_in_specs,
            out_specs=pl.BlockSpec((1, block), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((nblocks, block), out_dtype),
            interpret=interpret,
            compiler_params=params,
        )(t, enc_before, *operands)
    return vals, offs, cnts[:, 0], newe


@functools.partial(jax.jit, static_argnames=("bcap", "k_cap", "block",
                                             "out_dtype", "with_resid",
                                             "backend", "num_warps",
                                             "num_stages", "interpret"))
def compact_residual(g2d: jax.Array, e2d: jax.Array | None,
                     thres: jax.Array, *, bcap: int, k_cap: int,
                     block: int = 2048, out_dtype=jnp.float32,
                     with_resid: bool = True, backend: str = "interpret",
                     num_warps: int = 4, num_stages: int = 2,
                     interpret: bool = True):
    """One (or, on Triton, two) passes: staging buffers for the codec
    assembly + the new residual.

    Returns ``(vals, offs, counts, new_e2d)``; the first three match
    ``threshold_compact``'s contract (shared assembly), ``new_e2d`` is
    the (nblocks, block) residual with wire-surviving slots zeroed —
    or ``None`` with ``with_resid=False``, where the caller rebuilds the
    residual from the wire pair instead (the interpret-mode interpreter
    charges O(d) per grid step for carried outputs, so on CPU a k-sized
    XLA scatter onto ``u`` is cheaper than the in-kernel write).
    """
    nblocks, b = g2d.shape
    assert b == block and bcap % 8 == 0, (g2d.shape, block, bcap)
    operands = (g2d, e2d) if e2d is not None else (g2d,)
    params = compiler_params(backend, num_warps, num_stages)
    if backend == "triton":
        t = jnp.asarray(thres, jnp.float32).reshape(1, 1)
        return _compact_residual_triton(
            operands, t, nblocks=nblocks, block=block, bcap=bcap,
            k_cap=k_cap, out_dtype=out_dtype, with_resid=with_resid,
            interpret=interpret, params=params)

    # whole groups of blocks; the zero padding blocks select nothing
    group = min(GROUP, nblocks)
    steps = -(-nblocks // group)
    padded = steps * group
    rows = block_rows(block)
    data = tuple(jnp.pad(x, ((0, padded - nblocks), (0, 0))
                         ).reshape(-1, LANES) for x in operands)
    data_spec = pl.BlockSpec((group * rows, LANES), lambda i: (i, 0))
    out_specs = [
        pl.BlockSpec((group, bcap), lambda i: (i, 0)),
        pl.BlockSpec((group, bcap), lambda i: (i, 0)),
        pl.BlockSpec((group, LANES), lambda i: (i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((padded, bcap), jnp.float32),
        jax.ShapeDtypeStruct((padded, bcap), jnp.int32),
        jax.ShapeDtypeStruct((padded, LANES), jnp.int32),
    ]
    if with_resid:
        out_specs.append(data_spec)
        out_shape.append(jax.ShapeDtypeStruct((padded * rows, LANES),
                                              out_dtype))
    kern = functools.partial(_kernel, has_e=len(operands) == 2, bcap=bcap,
                             k_cap=k_cap, with_resid=with_resid, rows=rows,
                             group=group)
    outs = pl.pallas_call(
        kern,
        name="compact_residual",
        grid=(steps,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [data_spec] * len(data),
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
        compiler_params=params,
    )(jnp.asarray(thres, jnp.float32).reshape(1), *data)
    vals, offs, cnts = (o[:nblocks] for o in outs[:3])
    newe = (outs[3].reshape(padded, block)[:nblocks] if with_resid
            else None)
    return vals, offs, cnts[:, 0], newe
