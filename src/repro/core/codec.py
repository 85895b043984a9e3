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

``compact_by_mask`` finds each of the ``k_cap`` slots by a binary
search of ``cumsum(mask)`` and gathers its value, where
``k_cap * ceil(log2(d + 1)) <= d``; denser selections scatter every
element to its slot.  A TPU runs a scatter's ``d`` updates one at a
time (DESIGN.md §3), so the search is the form sparse selections take.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

SENTINEL = -1
# entries of the cumsum per step of the search's coarse first rounds
_STRIDE = 4096


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

    Both forms start from ``pos = cumsum(mask) - 1``, each selected
    element's slot.  Where the ``k_cap`` slots are few against ``d``
    (``k_cap * ceil(log2(d + 1)) <= d``, a static rule on the shapes),
    slot ``j``'s index is the first ``i`` with ``pos[i] >= j``: a
    lower-bound binary search, each round a ``k_cap``-sized gather, its
    first rounds on every ``_STRIDE``-th entry of ``pos`` and its last
    ``log2(_STRIDE) + 1`` inside the one stride that holds the answer;
    then one gather of the values.
    Otherwise every element scatters to its slot (the unselected and
    the surplus to a scratch slot cut off after): ``d`` writes, which a
    TPU performs one at a time (DESIGN.md §3), so it is kept for dense
    selections.  Both give the same bits.
    """
    d = u.shape[0]
    mask = mask.astype(jnp.int32)
    # position of each selected element in the compacted output
    pos = jnp.cumsum(mask) - 1
    if k_cap * d.bit_length() <= d:  # bit_length: ceil(log2(d + 1))
        return _compact_by_search(u, pos, k_cap)
    return _compact_by_scatter(u, mask, pos, k_cap)


def _compact_by_search(u: jax.Array, pos: jax.Array, k_cap: int):
    d = u.shape[0]
    slots = jnp.arange(k_cap, dtype=jnp.int32)
    # the first rounds search every _STRIDE-th entry of pos, the rest
    # search the one stride of pos that holds the answer
    block = jnp.searchsorted(pos[_STRIDE - 1::_STRIDE], slots, side="left",
                             method="scan").astype(jnp.int32)
    lo = block * _STRIDE
    at = _lower_bound(pos, slots, lo, jnp.minimum(lo + _STRIDE, d),
                      min(_STRIDE, d).bit_length())
    real = slots <= pos[-1]
    indices = jnp.where(real, at, SENTINEL)
    values = jnp.where(real, u[jnp.minimum(at, d - 1)],
                       jnp.zeros((), u.dtype))
    return values, indices


def _lower_bound(a: jax.Array, q: jax.Array, lo: jax.Array, hi: jax.Array,
                 rounds: int) -> jax.Array:
    """The first ``i`` in ``[lo, hi)`` with ``a[i] >= q`` (``hi`` if
    none), for a sorted ``a``, elementwise over ``q``; ``rounds`` must
    be at least ``ceil(log2(hi - lo + 1))``."""
    def halve(_, bounds):
        lo, hi = bounds
        mid = lo + (hi - lo) // 2
        left = a[jnp.minimum(mid, a.shape[0] - 1)] >= q
        return jnp.where(left, lo, mid + 1), jnp.where(left, mid, hi)

    return jax.lax.fori_loop(0, rounds, halve, (lo, hi))[1]


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
