"""The expert layer's grouped products: megablox's grouped matmul
(``gmm`` forward, ``gmm`` and ``tgmm`` backward) under a custom VJP
(DESIGN.md §17).

The kernel takes the Mosaic lowering on a TPU and the Pallas
interpreter elsewhere, as the EF kernels do (``ef_fused.tuning``'s
backend resolution, so ``use_backend`` reaches it too).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as kernel_gmm
from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm as kernel_tgmm

ROW_TILE = 128          # the kernel's row tile: row counts pad to it


def _tile(x: int, cap: int = 512) -> int:
    """The largest multiple of 128 that divides ``x`` and is at most
    ``cap``; ``x`` itself where it is no multiple of 128."""
    if x % 128:
        return x
    return max(t for t in range(128, min(x, cap) + 1, 128) if x % t == 0)


def _tiling(k: int, n: int):
    return (ROW_TILE, _tile(k), _tile(n))


def _interpret() -> bool:
    from repro.kernels.ef_fused.tuning import resolve_backend
    return resolve_backend() != "mosaic"


@jax.custom_vjp
def grouped_matmul(lhs, rhs, group_sizes, group_offset):
    """Rows of each held group of ``lhs`` (m, k) times that group's
    ``rhs`` (G, k, n), in float32; the groups are ``group_sizes`` (E,)
    from row 0, and ``rhs`` holds groups ``[group_offset, group_offset +
    G)``.  Rows of no held group are left unwritten.  Operands go to the
    kernel in ``rhs``'s dtype; cotangents come back in float32."""
    return _gmm(lhs, rhs, group_sizes, group_offset)


def _gmm(lhs, rhs, group_sizes, group_offset, transpose_rhs=False):
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return kernel_gmm(lhs.astype(rhs.dtype), rhs, group_sizes, jnp.float32,
                      _tiling(lhs.shape[1], n), group_offset,
                      transpose_rhs=transpose_rhs, interpret=_interpret())


def _grouped_fwd(lhs, rhs, group_sizes, group_offset):
    return (_gmm(lhs, rhs, group_sizes, group_offset),
            (lhs, rhs, group_sizes, group_offset))


def _grouped_bwd(res, g):
    lhs, rhs, group_sizes, group_offset = res
    g = g.astype(rhs.dtype)
    d_lhs = _gmm(g, rhs, group_sizes, group_offset, transpose_rhs=True)
    d_rhs = kernel_tgmm(lhs.astype(rhs.dtype).swapaxes(0, 1), g,
                        group_sizes, jnp.float32,
                        _tiling(lhs.shape[1], g.shape[1]),
                        group_offset, rhs.shape[0], interpret=_interpret())
    return d_lhs, d_rhs, None, None


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)
