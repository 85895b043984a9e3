"""Triton (GPU) kernel-shape bit-equality vs the reference lowering.

The ``backend="triton"`` lowering restructures all three fused-EF
kernels for a PARALLEL grid (per-block partials + an order-preserving
fold, and a two-phase compact/residual split) — see DESIGN.md §15.  On
the CPU CI runner every test here executes under the Pallas interpreter
(``exec_interpret``), which is exactly the coverage contract: the GPU
kernel STRUCTURE is bit-checked against the sequential reference shape
without a GPU.  Kernel geometry (block/stats_block/bcap) is pinned
wherever two backends are compared, so only the lowering differs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import codec
from repro.core.compression import CompressionConfig
from repro.core.compressors import get_compressor
from repro.dist import aggregate
from repro.dist.layout import build_layout, pack_residual_arrays
from repro.kernels.ef_fused import (count_passes, fused_compress_ef,
                                    tuning, use_backend)
from repro.kernels.ef_fused.compact_residual import compact_residual
from repro.kernels.ef_fused.fused_moments import fused_moments
from repro.kernels.ef_fused.segmented import (rows_compress_ef,
                                              segmented_compress_ef)
from repro.kernels.ef_fused.tree_count import tree_count
from repro.kernels.gaussian_topk.threshold_compact import SENTINEL
from repro.launch.mesh import make_mesh

BLOCK = 2048
FUSED = ("gaussiank", "gaussiank2", "histk")


def _u2d(seed, nblocks, block=BLOCK, dtype=jnp.float32):
    g = 0.02 * jax.random.normal(jax.random.PRNGKey(seed),
                                 (nblocks, block))
    e = 0.01 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                 (nblocks, block))
    return g.astype(dtype), e.astype(jnp.float32)


def _eq(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# kernel level: each pass bit-equal to the sequential reference shape
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("nblocks", [1, 5])
@pytest.mark.parametrize("with_hist", [False, True])
@pytest.mark.parametrize("with_e", [False, True])
def test_moments_partials_fold_bitwise(nblocks, with_hist, with_e):
    """Parallel per-block partials + the ordered fold reproduce the
    sequential accumulator bit-for-bit (the fold replays the exact
    left-to-right addition order; i32/absmax are associative)."""
    g, e = _u2d(3, nblocks)
    e = e if with_e else None
    ref = fused_moments(g, e, block=BLOCK, with_hist=with_hist,
                        backend="interpret", interpret=True)
    tri = fused_moments(g, e, block=BLOCK, with_hist=with_hist,
                        backend="triton", interpret=True)
    for r, t in zip(ref, tri):
        assert (r is None) == (t is None)
        if r is not None:
            _eq(r, t)


@pytest.mark.parametrize("nblocks", [1, 5])
def test_tree_count_partials_bitwise(nblocks):
    g, e = _u2d(7, nblocks)
    n_t = 7
    q = jnp.quantile(jnp.abs(g + e).reshape(-1),
                     jnp.linspace(0.5, 0.999, n_t)).astype(jnp.float32)
    ref = tree_count(g, e, q, n_t=n_t, block=BLOCK, backend="interpret",
                     interpret=True)
    tri = tree_count(g, e, q, n_t=n_t, block=BLOCK, backend="triton",
                     interpret=True)
    assert ref.shape == (n_t,) and ref.dtype == jnp.int32
    _eq(ref, tri)


@pytest.mark.parametrize("overflow", [False, True])
@pytest.mark.parametrize("with_resid", [False, True])
def test_compact_residual_two_phase_bitwise(overflow, with_resid):
    """The two-phase Triton split (stage sweep + cumsum + residual
    sweep) equals the single sequential sweep: same offsets/counts,
    same staged values on live slots, same residual — including bcap
    truncation (overflow) where the i32 prefix sums must agree."""
    nblocks, bcap, k_cap = 4, 64, 96
    g, e = _u2d(11, nblocks)
    if overflow:
        # block 1 stages > bcap elements: truncation prefix order matters
        g = g.at[1, 100:300].set(5.0)
    thres = jnp.float32(0.045)
    ref = compact_residual(g, e, thres, bcap=bcap, k_cap=k_cap,
                           block=BLOCK, with_resid=with_resid,
                           backend="interpret", interpret=True)
    tri = compact_residual(g, e, thres, bcap=bcap, k_cap=k_cap,
                           block=BLOCK, with_resid=with_resid,
                           backend="triton", interpret=True)
    vr, ofr, cr, er = ref
    vt, oft, ct, et = tri
    _eq(ofr, oft)
    _eq(cr, ct)
    # dead staging slots (offs == SENTINEL) may differ in zero SIGN
    # between the one-hot-matmul and masked-sum stagings; they never
    # reach the wire (assemble_staging drops them), so compare live only
    live = np.asarray(ofr) != SENTINEL
    assert live.sum() > 0
    _eq(np.asarray(vr)[live], np.asarray(vt)[live])
    if with_resid:
        _eq(er, et)
    else:
        assert er is None and et is None
    if overflow:
        assert int(np.asarray(cr)[1]) > bcap        # truncation exercised


# ---------------------------------------------------------------------------
# pipeline + segmented level, pinned geometry
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", FUSED)
@pytest.mark.parametrize("d", [257, 5000, 65536])
def test_pipeline_bitwise_vs_interpret(name, d):
    """Full fused pipeline, pinned geometry: the triton lowering returns
    the identical wire triple — values, indices AND residual."""
    k = max(1, d // 100)
    g = 0.02 * jax.random.normal(jax.random.PRNGKey(d), (d,))
    e = 0.01 * jax.random.normal(jax.random.PRNGKey(d + 1), (d,))
    kw = dict(block=BLOCK, stats_block=BLOCK, bcap=64)
    vr, ir, rr = fused_compress_ef(g, e, name, k, backend="interpret",
                                   **kw)
    vt, it, rt = fused_compress_ef(g, e, name, k, backend="triton", **kw)
    _eq(ir, it)
    _eq(vr, vt)
    _eq(rr, rt)
    # and conservation still holds exactly on the triton triple
    np.testing.assert_allclose(
        np.asarray(codec.decode(vt, it, d) + rt), np.asarray(g + e),
        atol=1e-7)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_pipeline_bitwise_edge_shapes(dtype):
    """Odd d, tiny d and bf16 leaves under the triton lowering."""
    for d, k in ((33, 3), (257, 5), (1, 1)):
        g = (0.02 * jax.random.normal(jax.random.PRNGKey(d), (d,))
             ).astype(dtype)
        e = 0.01 * jax.random.normal(jax.random.PRNGKey(d + 1), (d,))
        kw = dict(block=BLOCK, stats_block=BLOCK, bcap=64)
        ref = fused_compress_ef(g, e, "gaussiank", k,
                                backend="interpret", **kw)
        tri = fused_compress_ef(g, e, "gaussiank", k, backend="triton",
                                **kw)
        for r, t in zip(ref, tri):
            _eq(r, t)


def test_segmented_rows_bitwise():
    m, d_row = 2, 4096
    g = 0.02 * jax.random.normal(jax.random.PRNGKey(0), (m, 2 * d_row))
    e = 0.01 * jax.random.normal(jax.random.PRNGKey(1), (m, 2 * d_row))
    segs = [(0, d_row), (d_row, d_row)]
    ks, k_caps = [40, 40], [64, 64]
    ref = segmented_compress_ef(g, e, segs, "gaussiank", ks, k_caps,
                                backend="interpret")
    tri = segmented_compress_ef(g, e, segs, "gaussiank", ks, k_caps,
                                backend="triton")
    for (vr, ir, er), (vt, it, et) in zip(ref, tri):
        _eq(ir, it)
        _eq(vr, vt)
        _eq(er, et)
    r1 = rows_compress_ef(g[:, :d_row], e[:, :d_row], "gaussiank", 40,
                          k_cap=64, backend="triton")
    _eq(r1[1], tri[0][1])


def test_use_backend_context_reaches_kernels():
    """The context seam carries the backend through call stacks with no
    kernel kwargs — visible as the triton 4-pass accounting."""
    g = 0.02 * jax.random.normal(jax.random.PRNGKey(2), (20_000,))
    e = 0.01 * jax.random.normal(jax.random.PRNGKey(3), (20_000,))
    with use_backend("triton"):
        with count_passes() as pt:
            vc, ic, rc = fused_compress_ef(g, e, "gaussiank", 200)
    assert pt.by_label().get("residual_write") == 1, pt.records
    ve, ie, re = fused_compress_ef(g, e, "gaussiank", 200,
                                   backend="triton")
    _eq(ic, ie)
    _eq(vc, ve)
    _eq(rc, re)


def test_aggregate_bucketed_under_triton_context():
    """End-to-end dist-layer coverage (ISSUE 10 acceptance): the whole
    bucketed aggregation runs with the triton kernel shape forced via
    the context — same aggregate, residual and wire metrics as the
    default lowering (single-block leaves: identical fold order)."""
    from jax.sharding import PartitionSpec as P

    params = {"a": jnp.zeros((33, 5)), "n": {"b": jnp.zeros((7,)),
                                             "c": jnp.zeros((19, 3))}}
    key = jax.random.PRNGKey(0)
    grads = jax.tree.map(
        lambda p: 0.01 * jax.random.normal(
            jax.random.fold_in(key, p.size), p.shape), params)
    msize = 2
    spec = get_compressor("gaussiank")
    layout = build_layout(params, msize, 0.05, spec)
    resid = jax.tree.map(
        lambda e: 1e-3 * jax.random.normal(jax.random.PRNGKey(5), e.shape),
        aggregate.init_residuals(params, msize))
    flat_e = jnp.asarray(pack_residual_arrays(
        layout, [np.asarray(x) for x in jax.tree.leaves(resid)]))
    config = CompressionConfig(compressor="gaussiank", ratio=0.05,
                               backend="fused")
    mesh = make_mesh((1, 1), ("data", "model"))

    def bucketed(g, e):
        res = aggregate.aggregate_bucketed(
            g, e, layout, config, ("data",), "model",
            jax.random.PRNGKey(7), world=1)
        return res.agg, res.resid, res.metrics

    sm = jax.shard_map(bucketed, mesh=mesh, in_specs=(P(), P()),
                       out_specs=(P(), P(), P()), axis_names={"data"},
                       check_vma=False)
    out_ref = jax.jit(sm)(grads, flat_e)
    with use_backend("triton"):
        out_tri = jax.jit(sm)(grads, flat_e)
    assert tuning.resolve_backend(None, None) != "triton"  # popped
    for a, b in zip(jax.tree.leaves(out_ref[0]),
                    jax.tree.leaves(out_tri[0])):
        _eq(a, b)
    _eq(out_ref[1], out_tri[1])
    for mk in ("density", "comm_bits_sparse", "wire_bytes"):
        assert float(out_ref[2][mk]) == float(out_tri[2][mk]), mk
