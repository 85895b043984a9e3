"""Pallas kernel: multi-threshold count — the refinement loop in ONE pass.

Algorithm 1's refinement loop re-counts ``|u| > thres`` at a threshold
that depends on the previous count, which costs one HBM pass per
iteration (≤4).  But the reachable thresholds form a STATIC binary tree
rooted at the ppf estimate: every iteration either halves (count below
band) or 1.5×es (count above band) the current value, so after ``R``
iterations the loop can only ever have visited nodes of the depth-``R``
tree.  Counting ``|u| > t`` for all ``2^R − 1`` internal-node thresholds
in one fused pass lets the sequential refinement be replayed exactly on
the resulting count table without touching HBM again — identical
decisions, identical final threshold, 1 pass instead of ≤4.

Like pass A the kernel streams ``g`` (+ optional ``e``) as
``(block // 128, 128)`` tiles and forms ``u`` in registers.  The
sequential (mosaic) shape reads the thresholds from SMEM and keeps one
``(8, 128)`` i32 count tile per threshold in a revisited accumulator;
a grid step takes up to ``GROUP`` blocks.
The ``triton`` lowering writes per-block count rows instead (GPU grid
programs are parallel CTAs) and sums them outside the kernel — i32
addition is associative, so the combined counts are identical to the
sequential grid's.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ef_fused.tuning import (GROUP, LANES, acc_rows,
                                           block_rows, compiler_params,
                                           fold_tiles)


def _load_abs_u(g_ref, e_ref):
    x = g_ref[...].astype(jnp.float32)
    if e_ref is not None:
        x = x + e_ref[...].astype(jnp.float32)
    return jnp.abs(x)


def _kernel(t_ref, *refs, has_e: bool, n_t: int, sub: int):
    """Sequential-grid lowering: one revisited count tile per threshold."""
    acc_ref = refs[-1]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    absx = _load_abs_u(refs[0], refs[1] if has_e else None)
    for j in range(n_t):
        c = fold_tiles((absx > t_ref[j]).astype(jnp.int32), sub, jnp.add)
        acc_ref[j * sub:(j + 1) * sub] = acc_ref[j * sub:(j + 1) * sub] + c


def _partials_kernel(t_ref, *refs, has_e: bool, n_t: int):
    """Parallel-grid (Triton) lowering: each program owns an output row."""
    acc_ref = refs[-1]
    absx = _load_abs_u(refs[0], refs[1] if has_e else None).reshape(-1)
    t = t_ref[0, :n_t]                               # (n_t,) static slice
    c = jnp.sum((absx[None, :] > t[:, None]).astype(jnp.int32), axis=1)
    acc_ref[0, :] = jnp.concatenate([c, jnp.zeros((128 - n_t,), jnp.int32)])


@functools.partial(jax.jit, static_argnames=("n_t", "block", "backend",
                                             "num_warps", "num_stages",
                                             "interpret"))
def tree_count(g2d: jax.Array, e2d: jax.Array | None, thresholds: jax.Array,
               *, n_t: int, block: int = 2048, backend: str = "interpret",
               num_warps: int = 4, num_stages: int = 2,
               interpret: bool = True):
    """Counts of ``|g + e| > thresholds[j]`` for ``j < n_t`` — one pass.

    ``thresholds`` is a flat f32 vector of length ``n_t``.  Returns an
    ``(n_t,)`` i32 count vector.  ``backend`` picks the kernel shape (see
    module docstring); ``interpret`` picks the execution engine.
    """
    nblocks, b = g2d.shape
    assert b == block and 0 < n_t <= 128, (g2d.shape, block, n_t)
    rows = block_rows(block)
    sub = acc_rows(rows)
    has_e = e2d is not None
    data = tuple(x.reshape(-1, LANES)
                 for x in ((g2d, e2d) if has_e else (g2d,)))
    group = 1 if backend == "triton" else math.gcd(nblocks, GROUP)
    data_specs = [pl.BlockSpec((group * rows, LANES),
                               lambda i: (i, 0))] * len(data)
    params = compiler_params(backend, num_warps, num_stages)
    t = thresholds.astype(jnp.float32).reshape(n_t)
    if backend == "triton":
        acc = pl.pallas_call(
            functools.partial(_partials_kernel, has_e=has_e, n_t=n_t),
            name="tree_count_partials",
            grid=(nblocks,),
            in_specs=[pl.BlockSpec((1, 128), lambda i: (0, 0))] + data_specs,
            out_specs=pl.BlockSpec((1, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((nblocks, 128), jnp.int32),
            interpret=interpret,
            compiler_params=params,
        )(jnp.zeros((1, 128), jnp.float32).at[0, :n_t].set(t), *data)
        return jnp.sum(acc, axis=0)[:n_t]
    acc = pl.pallas_call(
        functools.partial(_kernel, has_e=has_e, n_t=n_t, sub=sub),
        name="tree_count",
        grid=(nblocks // group,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + data_specs,
        out_specs=pl.BlockSpec((n_t * sub, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_t * sub, LANES), jnp.int32),
        interpret=interpret,
        compiler_params=params,
    )(t, *data)
    return jnp.sum(acc.reshape(n_t, -1), axis=1)
