"""Compressor-zoo unit + property tests (paper §1 Eq. 2-4, §3.3 Alg. 1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (SENTINEL, bounds, codec, compress_with_ef,
                        compressors, decode, get_compressor, nnz)

ALL = compressors.available()


def _u(seed, d, scale=1.0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), (d,))


@pytest.mark.parametrize("name", ALL)
@pytest.mark.parametrize("d,k", [(1000, 10), (4096, 64), (333, 5)])
def test_error_feedback_conservation(name, d, k):
    """decode(comp(u)) + residual == u exactly (Eq. 2 invariant)."""
    spec = get_compressor(name)
    u = _u(0, d, 0.01)
    v, i, r = compress_with_ef(u, spec, k, jax.random.PRNGKey(1))
    np.testing.assert_allclose(np.asarray(decode(v, i, d) + r),
                               np.asarray(u), rtol=1e-6, atol=1e-8)


@pytest.mark.parametrize("name", ALL)
def test_values_match_indices(name):
    """Every encoded (value, index) pair satisfies values == u[idx]."""
    spec = get_compressor(name)
    u = _u(2, 2048)
    v, i = spec.select(u, 32, jax.random.PRNGKey(3))
    v, i = np.asarray(v), np.asarray(i)
    real = i != SENTINEL
    np.testing.assert_allclose(v[real], np.asarray(u)[i[real]], rtol=1e-6)
    assert np.all(v[~real] == 0)
    # indices unique among real entries
    assert len(set(i[real].tolist())) == real.sum()


def test_topk_exactness():
    u = _u(4, 1024)
    v, i = compressors.topk_select(u, 16)
    top_abs = np.sort(np.abs(np.asarray(u)))[-16:]
    np.testing.assert_allclose(np.sort(np.abs(np.asarray(v))), top_abs,
                               rtol=1e-6)


def test_topk_contraction_better_than_randk():
    """||u - Top_k(u)||^2 <= ||u - Rand_k(u)||^2 (paper Eq. 4)."""
    u = _u(5, 8192)
    for name, key in (("topk", None), ("randk", jax.random.PRNGKey(0))):
        spec = get_compressor(name)
        v, i = spec.select(u, 128, key)
        err = float(jnp.sum((u - decode(v, i, u.shape[0])) ** 2))
        if name == "topk":
            topk_err = err
        else:
            assert topk_err <= err


def test_gaussiank_accept_band():
    """Algorithm 1 keeps the selected count near k (band [2k/3, 4k/3])
    for Gaussian u with the two-sided correction."""
    u = _u(6, 100_000, 0.03)
    k = 500
    v, i = compressors.gaussiank_select(u, k, two_sided=True)
    c = int(nnz(i))
    assert 2 * k / 3 <= c <= 4 * k / 3 + 1, c


def test_gaussiank_cap():
    assert compressors.gaussiank_cap(99, 10_000) == 132
    assert compressors.gaussiank_cap(10_000, 10_000) == 10_000


def test_compact_by_mask_order_and_overflow():
    u = jnp.arange(10.0)
    mask = u % 2 == 1  # 5 elements
    v, i = codec.compact_by_mask(u, mask, 3)
    np.testing.assert_array_equal(np.asarray(i), [1, 3, 5])  # index order
    np.testing.assert_array_equal(np.asarray(v), [1, 3, 5])


def _compact_by_scatter_oracle(u, mask, k_cap):
    """The d-sized scatter form of ``compact_by_mask``: every element
    writes to its slot ``cumsum(mask) - 1``, the unselected and the
    surplus to a scratch slot ``k_cap`` that is cut off."""
    d = u.shape[0]
    mask = mask.astype(jnp.int32)
    pos = jnp.cumsum(mask) - 1
    slot = jnp.where((mask == 1) & (pos < k_cap), pos, k_cap)
    values = jnp.zeros((k_cap + 1,), u.dtype).at[slot].set(u, mode="drop")
    indices = jnp.full((k_cap + 1,), SENTINEL, jnp.int32).at[slot].set(
        jnp.arange(d, dtype=jnp.int32), mode="drop")
    return values[:k_cap], indices[:k_cap]


# (d, k_cap, mask density, or (slice, density) for a mask kept only in
# that slice); the row form (the older cases are named search) runs where
# k_cap * ceil(log2(d + 1)) <= d, the scatter form elsewhere
_COMPACT_CASES = {
    "search_sparse": (4096, 40, 0.005),
    "search_overflow": (4096, 40, 0.05),
    "search_k1": (4096, 1, 0.01),
    "search_at_rule": (4096, 315, 0.1),
    "scatter_past_rule": (4096, 316, 0.1),
    "scatter_full_k": (1000, 1000, 0.5),
    "search_empty": (2000, 30, 0.0),
    "search_full": (2000, 30, 1.0),
    "scatter_empty": (64, 64, 0.0),
    "scatter_full": (64, 20, 1.0),
    "search_d1": (1, 1, 1.0),
    "search_strides": (40_000, 500, 0.01),
    "search_strides_overflow": (40_000, 100, 0.01),
    # the row form's edges: rows padded, levels of counts added at 128**2
    # and 128**3, kept elements crowded into one row or its padding, and
    # overflow by none and by one
    "rows_d_unaligned": (1000, 40, 0.02),
    "rows_d_under_row": (100, 5, 0.1),
    "rows_d_128sq": (128 ** 2, 300, 0.01),
    "rows_d_128sq_plus1": (128 ** 2 + 1, 300, 0.01),
    "rows_d_128cube_minus1": (128 ** 3 - 1, 2000, 0.0005),
    "rows_d_128cube_plus1": (128 ** 3 + 1, 800, 0.0005),
    "rows_all_in_one_row": (4096, 40, (slice(256, 384), 0.2)),
    "rows_full_row_among_empty": (4096, 200, (slice(384, 512), 1.0)),
    "rows_only_last_padded_row": (1000, 40, (slice(896, 1000), 0.3)),
    "rows_overflow_at_cap": (4096, 100, (slice(5, 3705, 37), 1.0)),
    "rows_overflow_cap_plus1": (4096, 100, (slice(5, 3742, 37), 1.0)),
}


def test_row_levels_change_at_powers_of_128():
    assert [codec.row_levels(d) for d in (
        1, 128, 128 ** 2, 128 ** 2 + 1, 128 ** 3, 128 ** 3 + 1,
        205_520_896)] == [1, 1, 1, 2, 2, 3, 3]


@pytest.mark.parametrize("rows", [None, 3], ids=["row", "vmap3"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(_COMPACT_CASES))
def test_compact_by_mask_bits_match_scatter(case, dtype, rows):
    """``compact_by_mask`` gives the scatter form's bytes, values and
    indices, in both of its forms: index order, overflow keeping the
    lowest indices, sentinel slots holding +0."""
    d, k_cap, density = _COMPACT_CASES[case]
    assert (k_cap * d.bit_length() <= d) != case.startswith("scatter")
    rng = np.random.default_rng(list(_COMPACT_CASES).index(case))
    shape = (rows or 1, d)
    u = jnp.asarray(rng.normal(size=shape), dtype)
    if isinstance(density, tuple):
        where, density = density
        mask = np.zeros(shape, bool)
        mask[:, where] = rng.random(mask[:, where].shape) < density
        mask = jnp.asarray(mask)
    else:
        mask = jnp.asarray(rng.random(shape) < density)
    if rows is None:
        u, mask = u[0], mask[0]
        got = codec.compact_by_mask(u, mask, k_cap)
        want = _compact_by_scatter_oracle(u, mask, k_cap)
    else:
        got = jax.vmap(lambda a, m: codec.compact_by_mask(a, m, k_cap))(
            u, mask)
        want = jax.vmap(lambda a, m: _compact_by_scatter_oracle(
            a, m, k_cap))(u, mask)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes()


# levels of row counts -> leaves that get them, in each benchmark cell
_CELL_LEVELS = {
    "stablelm_efjnp_1chip": {1: 3, 3: 9},
    "dsv2lite_moe_1chip": {1: 7, 2: 3, 3: 17},
}


@pytest.mark.parametrize("workload", list(_CELL_LEVELS))
def test_benchmark_cells_compact_by_rows(workload):
    """Every leaf of the benchmark's cells, under its job's ratio and
    ``leaf_plan``, takes the row form of ``compact_by_mask`` (the form
    is a static rule on the leaf's shapes), with the levels of counts
    listed in ``_CELL_LEVELS``."""
    from collections import Counter
    from functools import partial

    from bench import spec, system
    from repro.core.compression import CompressionConfig
    from repro.dist.layout import build_layout
    from repro.models import init_params

    cell = spec.load(workload)
    job = cell["job"]
    params = jax.eval_shape(partial(init_params, system.model_config(
        cell["model"])), jax.random.PRNGKey(0))
    layout = build_layout(params, job["model_size"], CompressionConfig(
        compressor=job["compressor"], ratio=job["ratio"],
        strategy=job["strategy"], backend=job["backend"]))
    for seg in layout.segments:
        assert seg.k_cap * seg.d_row.bit_length() <= seg.d_row, seg
    assert Counter(codec.row_levels(seg.d_row)
                   for seg in layout.segments) == _CELL_LEVELS[workload]


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(10, 2000),
       st.integers(1, 50))
def test_property_ef_conservation_gaussiank(seed, d, k):
    k = min(k, d)
    u = _u(seed % 1000, d, 0.1)
    spec = get_compressor("gaussiank")
    v, i, r = compress_with_ef(u, spec, k)
    np.testing.assert_allclose(np.asarray(decode(v, i, d) + r),
                               np.asarray(u), rtol=1e-5, atol=1e-7)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(32, 4000),
       st.integers(1, 100))
def test_property_topk_bound_classic(seed, d, k):
    """||u - Top_k(u)||^2 <= (1 - k/d) ||u||^2 holds unconditionally."""
    k = min(k, d)
    u = _u(seed % 997, d)
    g = float(bounds.gamma_exact(u, k))
    assert g <= bounds.bound_classic(k, d) + 1e-6


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_property_paper_bound_gaussian(seed):
    """Theorem 1: for bell-shaped u, exact gamma <= (1-k/d)^2."""
    d, k = 20_000, 200
    u = _u(seed % 991, d)
    g = float(bounds.gamma_exact(u, k))
    assert g <= bounds.bound_paper(k, d) + 1e-6


def test_gaussiank_cap_edge_geometry():
    """k == d, k == 1 and tiny-d corners of the static capacity law."""
    # k == d: the 4k/3 over-allocation clamps to the vector itself
    assert compressors.gaussiank_cap(7, 7) == 7
    assert compressors.gaussiank_cap(1, 1) == 1
    # k == 1: ceil(4/3) == 2 slots (the refinement band upper edge)
    assert compressors.gaussiank_cap(1, 100) == 2
    # capacity never exceeds d even when 4k/3 rounds past it
    assert compressors.gaussiank_cap(6, 7) == 7
    for d in (1, 2, 3, 100):
        for k in range(1, d + 1):
            cap = compressors.gaussiank_cap(k, d)
            assert k <= cap + 1 and cap <= d  # band upper edge, clamped


@pytest.mark.parametrize("d,k", [
    (64, 64),    # k == d: sample is the whole vector, exact top-k
    (4096, 1),   # k == 1
    (3, 2),      # d smaller than the 1% sample floor
    (1, 1),      # degenerate single element
    (50, 49),    # sample stride d // s == 1
])
def test_dgck_select_edge_geometry(d, k):
    """DGC's sampled-threshold path at the corners where the sample
    stride or candidate cap degenerates: the codec contract must still
    hold and (for exact small cases) recover true top-k mass."""
    spec = get_compressor("dgck")
    u = _u(11, d, 0.5)
    v, i = spec.select(u, k, jax.random.PRNGKey(13))
    v, i = np.asarray(v), np.asarray(i)
    assert v.shape == (spec.k_cap(k, d),)
    real = i != SENTINEL
    assert np.all((i[real] >= 0) & (i[real] < d))
    assert len(set(i[real].tolist())) == int(real.sum())
    np.testing.assert_allclose(v[real], np.asarray(u)[i[real]], rtol=1e-6)
    if k == d:
        # whole vector sampled: the candidate threshold can drop nothing
        np.testing.assert_allclose(np.sort(np.abs(v)),
                                   np.sort(np.abs(np.asarray(u)))[-k:],
                                   rtol=1e-6)


@pytest.mark.parametrize("d,k", [(64, 64), (4096, 1), (3, 2), (1, 1),
                                 (50, 49)])
def test_rtopk_select_edge_geometry(d, k):
    """rTop-k at the same corners: the strided r-sample stays
    duplicate-free and the in-sample top-k fills exactly k real slots."""
    spec = get_compressor("rtopk")
    assert spec.k_cap(k, d) == min(d, k)
    r = compressors.rtopk_sample_size(k, d)
    assert k <= r <= d
    u = _u(17, d, 0.5)
    v, i = spec.select(u, k, jax.random.PRNGKey(19))
    v, i = np.asarray(v), np.asarray(i)
    assert np.all(i != SENTINEL), "rtopk returns exactly k real pairs"
    assert len(set(i.tolist())) == k
    np.testing.assert_allclose(v, np.asarray(u)[i], rtol=1e-6)
    if r == d:
        # sample covers the vector: in-sample top-k IS exact top-k
        np.testing.assert_allclose(np.sort(np.abs(v)),
                                   np.sort(np.abs(np.asarray(u)))[-k:],
                                   rtol=1e-6)


def test_strided_sample_duplicate_free():
    """The systematic sample underpinning dgck/rtopk: s distinct indices
    for every s <= d, including s == d and stride-1 geometries."""
    for d, s in [(10, 10), (10, 9), (7, 3), (1, 1), (4096, 41)]:
        idx = np.asarray(compressors._strided_sample(
            jax.random.PRNGKey(23), d, s))
        assert idx.shape == (s,)
        assert np.all((idx >= 0) & (idx < d))
        assert len(set(idx.tolist())) == s, (d, s)


def test_codec_roundtrip_sentinel():
    v = jnp.array([1.0, 2.0, 0.0])
    i = jnp.array([5, 2, SENTINEL], jnp.int32)
    dense = decode(v, i, 8)
    np.testing.assert_array_equal(np.asarray(dense),
                                  [0, 0, 2, 0, 0, 1, 0, 0])
    assert int(nnz(i)) == 2


def test_decode_add():
    v = jnp.array([1.0, 2.0])
    i = jnp.array([1, 1], jnp.int32)  # duplicate -> adds
    out = codec.decode_add(jnp.zeros(4), v, i)
    np.testing.assert_array_equal(np.asarray(out), [0, 3, 0, 0])
