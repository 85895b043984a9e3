"""Fixed-capacity sparse codec for gradient sparsification.

A compressed gradient is a pair ``(values, indices)`` of static shape
``(k_cap,)``.  Padding slots carry ``indices == SENTINEL`` (= -1) and
``values == 0``.  Static shapes are mandatory under XLA and make the
collective volume of the sparse all-gather a compile-time constant —
this is the TPU adaptation of the paper's variable-length GPU mask
writes (DESIGN.md §3).

The codec contract every producer/consumer relies on:

* **Sentinel handling** — a slot with ``index == SENTINEL`` is padding;
  its value MUST be 0 and decoders MUST skip it (both decoders below
  route sentinels to an out-of-range scatter slot dropped by XLA's
  ``mode="drop"``).
* **Duplicate indices** — decoding scatter-*adds*, so a coordinate that
  appears in several slots (or in several workers' pairs summed into one
  buffer) accumulates; this is what makes the decode-sum of all workers'
  pairs equal the sum of their decoded gradients.
* **Capacity overflow** — encoders never emit more than ``k_cap`` real
  slots.  ``compact_by_mask`` truncates deterministically (lowest
  indices win) and the surplus mass must stay in the caller's
  error-feedback residual via the conservation identity
  ``u == decode(encode(u)) + residual``.

``compact_by_mask`` finds each of the ``k_cap`` slots by walking down
counts of the mask's rows of 128, where ``k_cap * ceil(log2(d + 1)) <=
d``; denser selections scatter every element to its slot.  A TPU runs
a scatter's ``d`` updates one at a time, and a prefix sum over ``d``
elements or a gather of one element at a time slowly too (DESIGN.md
§3), so the sparse form reads ``d`` only in dense row reductions and
gathers whole rows of 128.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

SENTINEL = -1
# entries of a row of counts: the lanes of a TPU vector register
_ROW = 128


@jax.named_scope("ef.compact")
def compact_by_mask(u: jax.Array, mask: jax.Array, k_cap: int):
    """Compact the masked elements of ``u`` into a fixed ``(k_cap,)`` buffer.

    Elements are kept in index order.  Capacity overflow: if more than
    ``k_cap`` elements are masked, the surplus (highest indices) is
    dropped — by the conservation identity the dropped mass lands in the
    error-feedback residual, which re-submits it next step (DESIGN.md
    §3: over-selection only ever costs one step of staleness).

    Returns ``(values, indices)`` with sentinel padding: unused slots
    carry ``indices == SENTINEL`` and ``values == 0``.  Real indices are
    strictly increasing, hence duplicate-free.

    Where the ``k_cap`` slots are few against ``d`` (``k_cap *
    ceil(log2(d + 1)) <= d``, a static rule on the shapes), slot ``j``'s
    element is found by :func:`_compact_by_rows`: counts of the mask's
    rows of 128, counts of those counts' rows, up to a level of at most
    128, walked down with one gather of a 128-wide row a level.
    Otherwise every element scatters to its slot ``cumsum(mask) - 1``
    (the unselected and the surplus to a scratch slot cut off after):
    ``d`` writes, which a TPU performs one at a time (DESIGN.md §3), so
    it is kept for dense selections.  Both give the same bits.
    """
    d = u.shape[0]
    if k_cap * d.bit_length() <= d:  # bit_length: ceil(log2(d + 1))
        return _compact_by_rows(u, mask, k_cap)
    mask = mask.astype(jnp.int32)
    # position of each selected element in the compacted output
    return _compact_by_scatter(u, mask, jnp.cumsum(mask) - 1, k_cap)


def row_levels(d: int) -> int:
    """The levels of counts :func:`_compact_by_rows` builds over ``d``
    elements: the first counts each row of 128 elements, each next one
    each row of 128 counts of the one before, the last has at most 128
    entries (1 up to ``d = 128**2``, 2 up to ``128**3``, ...)."""
    levels, n = 1, -(-d // _ROW)
    while n > _ROW:
        levels, n = levels + 1, -(-n // _ROW)
    return levels


def _rows(a: jax.Array) -> jax.Array:
    """``a`` zero-padded to whole rows of ``_ROW``, as ``(rows, _ROW)``."""
    return jnp.pad(a, (0, -a.shape[0] % _ROW)).reshape(-1, _ROW)


def _compact_by_rows(u: jax.Array, mask: jax.Array, k_cap: int):
    """Slot ``j``'s element by a walk down row counts of the mask.

    The mask, as ``(d / 128, 128)`` rows, is reduced to its count of
    kept elements per row, those counts to theirs per row of 128, and
    so on (:func:`row_levels`): dense reductions, one read of the mask,
    nothing ``d``-sized but an int8 copy of it (and, where ``d`` is not
    a multiple of 128, a padded copy of ``u``).  Every slot compares
    itself with the inclusive prefix of the top level (at most 128
    entries) to pick its child; at each level below, one gather fetches
    the chosen child's 128-wide row of in-row prefixes, and the entries
    not above what is left of ``j`` pick the next child.  At the bottom
    the slot's mask row and ``u`` row are gathered: an exact triangular
    matmul gives the mask row's prefix, which gives the column, and a
    one-hot select of the ``u`` row's bits the value.
    """
    slots = jnp.arange(k_cap, dtype=jnp.int32)
    mask_rows = _rows(mask.astype(jnp.int8))
    counts = [mask_rows.sum(axis=1, dtype=jnp.int32)]
    for _ in range(row_levels(u.shape[0]) - 1):
        counts.append(_rows(counts[-1]).sum(axis=1))
    # the top level, against every slot: its child, and the kept
    # elements before that child
    top = jnp.cumsum(counts.pop())
    below = top <= slots[:, None]
    row = below.sum(axis=1, dtype=jnp.int32)
    base = jnp.max(jnp.where(below, top, 0), axis=1)
    for level in reversed(counts):
        prefix = jnp.take(jnp.cumsum(_rows(level), axis=1), row, axis=0,
                          mode="clip")
        below = prefix <= (slots - base)[:, None]
        row = row * _ROW + below.sum(axis=1, dtype=jnp.int32)
        base = base + jnp.max(jnp.where(below, prefix, 0), axis=1)
    kept = jnp.take(mask_rows, row, axis=0, mode="clip")
    # in-row prefix by a matmul of 0/1 operands: exact in bf16, summed
    # in f32 (at most 128)
    upper = jnp.triu(jnp.ones((_ROW, _ROW), jnp.bfloat16))
    prefix = jnp.matmul(kept.astype(jnp.bfloat16), upper,
                        preferred_element_type=jnp.float32)
    col = (prefix <= (slots - base)[:, None].astype(jnp.float32)).sum(
        axis=1, dtype=jnp.int32)
    values = _pick(jnp.take(_rows(u), row, axis=0, mode="clip"), col)
    real = slots < top[-1]
    indices = jnp.where(real, row * _ROW + col, SENTINEL)
    values = jnp.where(real, values, jnp.zeros((), u.dtype))
    return values, indices


def _pick(rows: jax.Array, col: jax.Array) -> jax.Array:
    """``rows[i, col[i]]``, bit for bit, by a one-hot select of the
    elements' bits and a row reduction (``take_along_axis`` would
    compile to one more gather of one element a slot)."""
    bits = jnp.dtype(f"int{8 * rows.dtype.itemsize}")
    hot = jnp.arange(_ROW, dtype=jnp.int32) == col[:, None]
    # one nonzero term a row: the sum is exact in the elements' width
    picked = jnp.sum(jnp.where(hot, jax.lax.bitcast_convert_type(rows, bits),
                               0), axis=1, dtype=bits)
    return jax.lax.bitcast_convert_type(picked, rows.dtype)


def _compact_by_scatter(u: jax.Array, mask: jax.Array, pos: jax.Array,
                        k_cap: int):
    d = u.shape[0]
    keep = (mask == 1) & (pos < k_cap)
    # overflow / unselected elements all write to the scratch slot k_cap
    slot = jnp.where(keep, pos, k_cap)
    values = jnp.zeros((k_cap + 1,), u.dtype).at[slot].set(u, mode="drop")
    indices = jnp.full((k_cap + 1,), SENTINEL, jnp.int32).at[slot].set(
        jnp.arange(d, dtype=jnp.int32), mode="drop"
    )
    return values[:k_cap], indices[:k_cap]


def decode(values: jax.Array, indices: jax.Array, d: int) -> jax.Array:
    """Scatter a compressed ``(values, indices)`` pair back to dense ``(d,)``.

    Sentinel slots (``index == SENTINEL``) contribute nothing — they are
    rewritten to the out-of-range slot ``d`` with value 0 and dropped by
    the scatter.  Duplicate real indices scatter-*add* (the §3 contract);
    pairs produced by this module's encoders are duplicate-free, but
    merged/relayed pairs (dist/aggregate.py) rely on additivity.
    """
    safe = jnp.where(indices == SENTINEL, d, indices)
    return jnp.zeros((d,), values.dtype).at[safe].add(
        jnp.where(indices == SENTINEL, 0, values), mode="drop"
    )


def decode_add(dense: jax.Array, values: jax.Array, indices: jax.Array) -> jax.Array:
    """Scatter-*add* a compressed pair into an existing dense buffer.

    Same sentinel and duplicate-index semantics as :func:`decode`
    (sentinels vanish, duplicates accumulate); ``dense`` supplies the
    accumulation base and the output length.
    """
    d = dense.shape[0]
    safe = jnp.where(indices == SENTINEL, d, indices)
    return dense.at[safe].add(
        jnp.where(indices == SENTINEL, 0, values), mode="drop"
    )


def offset_indices(indices: jax.Array, offset: int) -> jax.Array:
    """Shift the real indices of a pair by ``offset``, sentinel-aware.

    The bucket-globalization primitive (DESIGN.md §10): a leaf segment's
    row-local indices become bucket-global by adding the segment's static
    column offset; sentinel slots stay ``SENTINEL`` so decoders keep
    skipping them.  Decoding the concatenated wire block of several
    segments then scatters each segment into its own disjoint column
    range — elementwise equal to decoding every segment on its own.
    """
    return jnp.where(indices == SENTINEL, SENTINEL, indices + offset)


def nnz(indices: jax.Array) -> jax.Array:
    """Number of real (non-sentinel) slots in a compressed pair.

    Counts occupancy, not distinct coordinates: a duplicated index (legal
    in merged pairs) counts once per slot it occupies.
    """
    return jnp.sum((indices != SENTINEL).astype(jnp.int32))
