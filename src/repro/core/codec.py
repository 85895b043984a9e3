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
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

SENTINEL = -1


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
    """
    d = u.shape[0]
    mask = mask.astype(jnp.int32)
    # position of each selected element in the compacted output
    pos = jnp.cumsum(mask) - 1
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
