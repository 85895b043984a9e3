"""BucketLayout subsystem (DESIGN.md §10): static geometry, pack/unpack
roundtrips, stable leaf-path RNG salts, worker-local and single-device
end-to-end bit-identity of the bucketed pipeline against the per-leaf
oracle, and the jaxpr collective-count acceptance check (one wire
message per level per step, independent of leaf count — traced over an
AbstractMesh, so no devices needed).  The multi-device bit-identity runs
live in tests/_dist_check.py ``bucketed`` (slow job)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.core import codec, get_compressor
from repro.core.adaptk import make_policy
from repro.core.compression import CompressionConfig
from repro.dist import aggregate
from repro.dist.layout import (build_chunk_plan, build_layout, chunk_view,
                               collective_count, flat_dims, leaf_key_salt,
                               pack_grads, pack_residual_arrays,
                               unpack_residual_arrays, unpack_tree,
                               validate_chunk_plan)
from repro.launch.hlo_cost import count_wire_collectives
from repro.launch.mesh import make_mesh

MSIZE, RATIO = 2, 0.05


def _params(extra=False):
    p = {"a": jnp.zeros((33, 5)), "n": {"b": jnp.zeros((7,)),
                                        "c": jnp.zeros((19, 3))}}
    if extra:
        p["n"]["bb"] = jnp.zeros((11,))   # sorts between "b" and "c"
    return p


def _grads(params, seed=0):
    k = jax.random.PRNGKey(seed)
    return jax.tree.map(
        lambda p: 0.01 * jax.random.normal(
            jax.random.fold_in(k, p.size + p.shape[0]), p.shape), params)


def _resid_tree(params, seed=5, scale=1e-3):
    tree = aggregate.init_residuals(params, MSIZE)
    return jax.tree.map(
        lambda e: scale * jax.random.normal(jax.random.PRNGKey(seed),
                                            e.shape), tree)


def _flatten_resid(layout, tree):
    return jnp.asarray(pack_residual_arrays(
        layout, [np.asarray(x) for x in jax.tree.leaves(tree)]))


# ---------------------------------------------------------------------------
# geometry
# ---------------------------------------------------------------------------


def test_layout_geometry_prefix_sums():
    spec = get_compressor("topk")
    params = _params()
    layout = build_layout(params, MSIZE, RATIO, spec)
    assert len(layout.segments) == len(jax.tree.leaves(params))
    row_off = cap_off = 0
    for seg, leaf in zip(layout.segments, jax.tree.leaves(params)):
        d_pad, d_row = flat_dims(leaf.size, MSIZE)
        assert (seg.size, seg.d_pad, seg.d_row) == (leaf.size, d_pad, d_row)
        assert seg.row_off == row_off and seg.cap_off == cap_off
        _, _, k_row, k_cap = aggregate.leaf_plan(leaf.size, MSIZE, RATIO,
                                                 spec)
        assert (seg.k_row, seg.k_cap) == (k_row, k_cap)
        row_off += seg.d_row
        cap_off += seg.k_cap
    assert layout.d_row_total == row_off
    assert layout.k_cap_total == cap_off
    assert layout.flat_size == MSIZE * row_off
    assert layout.d_total == sum(x.size for x in jax.tree.leaves(params))


def test_layout_wire_accounting_matches_per_leaf_formula():
    spec = get_compressor("gaussiank")
    layout = build_layout(_params(), MSIZE, RATIO, spec)
    for strat, world, pods in (("allgather", 8, 1), ("gtopk", 8, 1),
                               ("hierarchical", 8, 2)):
        per_leaf = sum(
            aggregate.strategy_wire_pairs(strat, world, pods)
            * MSIZE * s.k_cap * 64 for s in layout.segments)
        assert layout.comm_bits_sparse(strat, world, pods) == per_leaf
    assert layout.collectives("allgather", 8) == 1
    assert layout.collectives("hierarchical", 8, 2) == 2
    assert layout.collectives("gtopk", 8) == 3
    assert collective_count("gtopk", 8, leaves=10) == 30


def test_layout_validation_errors():
    spec = get_compressor("topk")
    layout = build_layout(_params(), MSIZE, RATIO, spec)
    with pytest.raises(ValueError):
        build_layout({}, MSIZE, RATIO, spec)
    with pytest.raises(ValueError):   # wrong leaf count
        pack_grads(layout, {"a": jnp.zeros((33, 5))}, jnp.float32)
    with pytest.raises(ValueError):   # wrong compressor
        aggregate.aggregate_bucketed(
            _grads(_params()), jnp.zeros((layout.flat_size,)), layout,
            CompressionConfig(compressor="randk", ratio=RATIO),
            ("data",), "model", jax.random.PRNGKey(0))
    with pytest.raises(ValueError):   # adaptive mode mismatch
        aggregate.aggregate_bucketed(
            _grads(_params()), jnp.zeros((layout.flat_size,)), layout,
            CompressionConfig(compressor="topk", ratio=RATIO,
                              density_policy=make_policy("variance")),
            ("data",), "model", jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# chunk plan geometry (DESIGN.md §11)
# ---------------------------------------------------------------------------


def test_chunk_plan_tiles_layout_exactly():
    spec = get_compressor("topk")
    layout = build_layout(_params(extra=True), MSIZE, RATIO, spec)
    n_segs = len(layout.segments)
    for n in range(1, n_segs + 3):       # over-request clamps to n_segs
        plan = build_chunk_plan(layout, n)
        assert plan.requested == n
        assert plan.n_chunks == min(n, n_segs)
        assert plan.n_chunks == len(plan.groups)
        validate_chunk_plan(layout, plan)    # contiguous leaf-aligned tiling
        seg = row = cap = 0
        for i, grp in enumerate(plan.groups):
            assert grp.index == i
            assert grp.seg_lo == seg and grp.row_off == row \
                and grp.cap_off == cap
            assert grp.seg_hi > grp.seg_lo   # never an empty group
            seg, row, cap = (grp.seg_hi, row + grp.d_row,
                             cap + grp.k_cap)
        assert seg == n_segs
        assert row == layout.d_row_total and cap == layout.k_cap_total


def test_chunk_plan_balances_rows():
    """The greedy cut must not produce a degenerate split: with equal
    leaves every group's row span stays within one leaf of d_row/N."""
    spec = get_compressor("topk")
    params = {f"p{i}": jnp.zeros((64,)) for i in range(8)}
    layout = build_layout(params, 1, RATIO, spec)
    for n in (2, 4):
        plan = build_chunk_plan(layout, n)
        for grp in plan.groups:
            assert grp.d_row == layout.d_row_total // n


def test_chunk_view_is_rebased_sublayout():
    spec = get_compressor("topk")
    layout = build_layout(_params(), MSIZE, RATIO, spec)
    plan = build_chunk_plan(layout, 2)
    seen = []
    for grp in plan.groups:
        view = chunk_view(layout, grp)
        assert view.d_row_total == grp.d_row
        assert view.k_cap_total == grp.k_cap
        assert view.flat_size == MSIZE * grp.d_row
        assert len(view.segments) == grp.seg_hi - grp.seg_lo
        for sub, orig in zip(view.segments,
                             layout.segments[grp.seg_lo:grp.seg_hi]):
            # window-local offsets, but identical identity: the RNG salt
            # and selection plan must be untouched so per-chunk
            # compression is bit-identical to the unchunked pass
            assert sub.row_off == orig.row_off - grp.row_off
            assert sub.cap_off == orig.cap_off - grp.cap_off
            assert (sub.name, sub.salt) == (orig.name, orig.salt)
            assert (sub.k_row, sub.k_cap) == (orig.k_row, orig.k_cap)
            seen.append(sub.name)
    assert seen == [s.name for s in layout.segments]


def test_chunk_plan_validation_errors():
    spec = get_compressor("topk")
    layout = build_layout(_params(), MSIZE, RATIO, spec)
    with pytest.raises(ValueError):
        build_chunk_plan(layout, 0)
    plan = build_chunk_plan(layout, 2)
    with pytest.raises(ValueError):   # plan from a different layout
        other = build_layout(_params(extra=True), MSIZE, RATIO, spec)
        validate_chunk_plan(other, plan)
    with pytest.raises(ValueError):   # chunked agg rejects a stale plan
        aggregate.aggregate_bucketed_chunked(
            _grads(_params(extra=True)),
            jnp.zeros((other.flat_size,)), other, plan,
            CompressionConfig(compressor="topk", ratio=RATIO),
            ("data",), "model", jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# stable RNG salts
# ---------------------------------------------------------------------------


def test_leaf_salts_stable_under_insertion():
    """Adding a parameter must not reshuffle other leaves' RNG salts —
    the fix for the fold_in(key, flatten_index) keying bug."""
    spec = get_compressor("topk")
    base = build_layout(_params(), MSIZE, RATIO, spec)
    grown = build_layout(_params(extra=True), MSIZE, RATIO, spec)
    base_salts = {s.name: s.salt for s in base.segments}
    grown_salts = {s.name: s.salt for s in grown.segments}
    for name, salt in base_salts.items():
        assert grown_salts[name] == salt
    # the inserted leaf shifts flatten indices of everything after it
    base_idx = {s.name: i for i, s in enumerate(base.segments)}
    grown_idx = {s.name: i for i, s in enumerate(grown.segments)}
    assert any(base_idx[n] != grown_idx[n] for n in base_idx)
    # deterministic across processes (blake2s, not hash())
    assert leaf_key_salt("n/c") == leaf_key_salt("n/c")
    assert 0 <= leaf_key_salt("n/c") < 2 ** 31


def test_per_leaf_randk_unchanged_by_unrelated_leaf():
    """aggregate_compressed with a keyed compressor selects the same
    coordinates for leaf "a" whether or not an unrelated leaf exists."""
    mesh = make_mesh((1, 1), ("data", "model"))

    def run(params):
        grads = _grads(params)
        resid = _resid_tree(params)

        def body(g, e):
            res = aggregate.aggregate_compressed(
                g, e, CompressionConfig(compressor="randk", ratio=RATIO),
                ("data",), "model", MSIZE, jax.random.PRNGKey(7), world=1)
            return res.agg
        sm = jax.shard_map(body, mesh=mesh, in_specs=(P(), P()),
                           out_specs=P(), axis_names={"data"},
                           check_vma=False)
        return jax.jit(sm)(grads, resid)

    small = run(_params())
    grown = run(_params(extra=True))
    np.testing.assert_array_equal(np.asarray(small["a"]),
                                  np.asarray(grown["a"]))
    np.testing.assert_array_equal(np.asarray(small["n"]["c"]),
                                  np.asarray(grown["n"]["c"]))


# ---------------------------------------------------------------------------
# pack / unpack
# ---------------------------------------------------------------------------


def test_pack_unpack_grads_roundtrip():
    spec = get_compressor("topk")
    params = _params()
    layout = build_layout(params, MSIZE, RATIO, spec)
    grads = _grads(params)
    bucket = pack_grads(layout, grads, jnp.float32)
    assert bucket.shape == (MSIZE, layout.d_row_total)
    back = unpack_tree(layout, bucket, like=grads)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # per-segment view == the per-leaf pad+reshape, bitwise
    for seg, g in zip(layout.segments, jax.tree.leaves(grads)):
        rows = np.pad(np.asarray(g).reshape(-1),
                      (0, seg.d_pad - seg.size)).reshape(MSIZE, seg.d_row)
        np.testing.assert_array_equal(
            np.asarray(bucket[:, seg.row_off:seg.row_off + seg.d_row]),
            rows)


def test_pack_residual_arrays_roundtrip_with_worker_axis():
    spec = get_compressor("topk")
    params = _params()
    layout = build_layout(params, MSIZE, RATIO, spec)
    rng = np.random.default_rng(0)
    arrs = [rng.normal(size=(3, s.d_pad)).astype(np.float32)
            for s in layout.segments]
    flat = pack_residual_arrays(layout, arrs)
    assert flat.shape == (3, layout.flat_size)
    back = unpack_residual_arrays(layout, flat)
    for a, b in zip(arrs, back):
        np.testing.assert_array_equal(a, b)


def test_pack_residual_arrays_fails_loudly():
    spec = get_compressor("topk")
    layout = build_layout(_params(), MSIZE, RATIO, spec)
    good = [np.zeros((s.d_pad,), np.float32) for s in layout.segments]
    with pytest.raises(ValueError):        # truncated leaf
        bad = list(good)
        bad[1] = bad[1][:-1]
        pack_residual_arrays(layout, bad)
    with pytest.raises(ValueError):        # missing leaf
        pack_residual_arrays(layout, good[:-1])
    with pytest.raises(ValueError):        # inconsistent worker dims
        bad = [np.zeros((2, s.d_pad), np.float32)
               for s in layout.segments]
        bad[0] = np.zeros((3, layout.segments[0].d_pad), np.float32)
        pack_residual_arrays(layout, bad)
    with pytest.raises(ValueError):        # wrong flat size
        unpack_residual_arrays(layout, np.zeros((7,), np.float32))


# ---------------------------------------------------------------------------
# worker-local bit-identity: bucket_compress == concat(compress_worker)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,backend,codec_dtype", [
    ("topk", "reference", None),
    ("randk", "reference", None),
    ("gaussiank", "reference", jnp.bfloat16),
    ("gaussiank", "auto", None),           # fused segmented pipeline
])
def test_bucket_compress_matches_per_leaf(name, backend, codec_dtype):
    spec = get_compressor(name)
    params = _params()
    layout = build_layout(params, MSIZE, RATIO, spec)
    grads = _grads(params)
    resid = _resid_tree(params)
    key = jax.random.PRNGKey(3)

    G = pack_grads(layout, grads, jnp.float32)
    E = _flatten_resid(layout, resid).reshape(MSIZE, layout.d_row_total)
    values, indices, new_E, _ = aggregate.bucket_compress(
        G, E, layout, spec, key, codec_dtype=codec_dtype, backend=backend)
    assert values.shape == (MSIZE, layout.k_cap_total)

    for seg, g, e in zip(layout.segments, jax.tree.leaves(grads),
                         jax.tree.leaves(resid)):
        lkey = jax.random.fold_in(key, seg.salt)
        v, i, ne, _ = aggregate.compress_worker(
            g, e, spec, RATIO, MSIZE, lkey, codec_dtype=codec_dtype,
            backend=backend)
        sl = slice(seg.cap_off, seg.cap_off + seg.k_cap)
        np.testing.assert_array_equal(np.asarray(values[:, sl]),
                                      np.asarray(v), err_msg=seg.name)
        np.testing.assert_array_equal(
            np.asarray(indices[:, sl]),
            np.asarray(codec.offset_indices(i, seg.row_off)),
            err_msg=seg.name)
        rs = slice(seg.row_off, seg.row_off + seg.d_row)
        np.testing.assert_array_equal(
            np.asarray(new_E[:, rs]).reshape(-1), np.asarray(ne),
            err_msg=seg.name)


# ---------------------------------------------------------------------------
# end-to-end bit-identity on a single-device mesh (tier-1; the (4,2) and
# (2,2,2) runs live in the slow job — tests/_dist_check.py bucketed)
# ---------------------------------------------------------------------------


def _run_both(params, strategy, *, mesh_shape=(1, 1),
              axes_names=("data", "model"), density_policy=None,
              momentum_correction=0.0, with_r2=False,
              codec_dtype=None, backend="reference", name="topk"):
    spec = get_compressor(name)
    layout = build_layout(params, MSIZE, RATIO, spec,
                          density_policy=density_policy)
    grads = _grads(params)
    resid = _resid_tree(params)
    r2 = _resid_tree(params, seed=11, scale=5e-4) if with_r2 else None
    mesh = make_mesh(mesh_shape, axes_names)
    data_axes = tuple(a for a in axes_names if a != "model")
    config = CompressionConfig(
        compressor=name, ratio=RATIO, strategy=strategy,
        codec_dtype=codec_dtype, momentum_correction=momentum_correction,
        backend=backend, density_policy=density_policy)
    kw = dict(world=1, step=jnp.int32(0) if density_policy else None)

    def per_leaf(g, e, *r2s):
        res = aggregate.aggregate_compressed(
            g, e, config, data_axes, "model", MSIZE,
            jax.random.PRNGKey(7), resid2=r2s[0] if r2s else None, **kw)
        return ((res.agg, res.resid, res.metrics)
                + ((res.resid2,) if r2s else ()))

    def bucketed(g, e, *r2s):
        res = aggregate.aggregate_bucketed(
            g, e, layout, config, data_axes, "model",
            jax.random.PRNGKey(7), resid2=r2s[0] if r2s else None, **kw)
        return ((res.agg, res.resid, res.metrics)
                + ((res.resid2,) if r2s else ()))

    n_out = 4 if with_r2 else 3
    sm1 = jax.shard_map(per_leaf, mesh=mesh,
                        in_specs=(P(),) * (2 + with_r2),
                        out_specs=(P(),) * n_out,
                        axis_names=set(data_axes), check_vma=False)
    sm2 = jax.shard_map(bucketed, mesh=mesh,
                        in_specs=(P(),) * (2 + with_r2),
                        out_specs=(P(),) * n_out,
                        axis_names=set(data_axes), check_vma=False)
    args1 = (grads, resid) + ((r2,) if with_r2 else ())
    flat_e = _flatten_resid(layout, resid)
    args2 = (grads, flat_e) + (
        (_flatten_resid(layout, r2),) if with_r2 else ())
    out1 = jax.jit(sm1)(*args1)
    out2 = jax.jit(sm2)(*args2)

    for a, b in zip(jax.tree.leaves(out1[0]), jax.tree.leaves(out2[0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        pack_residual_arrays(layout, [np.asarray(x)
                                      for x in jax.tree.leaves(out1[1])]),
        np.asarray(out2[1]))
    for mk in ("density", "density_cap", "comm_bits_sparse",
               "comm_bits_dense", "wire_bytes"):
        assert float(out1[2][mk]) == float(out2[2][mk]), mk
    if density_policy is not None:
        assert float(out1[2]["k_total"]) == float(out2[2]["k_total"])
    if with_r2:
        np.testing.assert_array_equal(
            pack_residual_arrays(layout, [np.asarray(x) for x in
                                          jax.tree.leaves(out1[3])]),
            np.asarray(out2[3]))
    # the dispatch-count claim, as a metric
    L = len(jax.tree.leaves(params))
    eff = strategy if (strategy != "hierarchical" or with_r2
                       and len(data_axes) > 1) else "allgather"
    assert float(out1[2]["collectives_per_step"]) == collective_count(
        eff, 1, 1, leaves=L)
    assert float(out2[2]["collectives_per_step"]) == collective_count(
        eff, 1, 1)


@pytest.mark.parametrize("strategy", ["allgather", "gtopk"])
def test_bucketed_end_to_end_fixed_k(strategy):
    _run_both(_params(), strategy)


def test_bucketed_runtime_grad_dtype_wins_over_layout_dtype():
    """A layout built from bf16 params fed f32 gradients must return f32
    aggregates and size comm_bits_dense from the runtime dtype — the
    per-leaf path's contract (`.astype(g.dtype)`)."""
    spec = get_compressor("topk")
    params16 = jax.tree.map(lambda p: p.astype(jnp.bfloat16), _params())
    layout = build_layout(params16, MSIZE, RATIO, spec)
    grads = _grads(_params())          # f32, same shapes
    resid = _resid_tree(_params())
    mesh = make_mesh((1, 1), ("data", "model"))

    config = CompressionConfig(compressor="topk", ratio=RATIO,
                               backend="reference")

    def bucketed(g, e):
        res = aggregate.aggregate_bucketed(
            g, e, layout, config, ("data",), "model",
            jax.random.PRNGKey(7), world=1)
        return res.agg, res.metrics

    def per_leaf(g, e):
        res = aggregate.aggregate_compressed(
            g, e, config, ("data",), "model", MSIZE,
            jax.random.PRNGKey(7), world=1)
        return res.agg, res.metrics

    sm2 = jax.shard_map(bucketed, mesh=mesh, in_specs=(P(), P()),
                        out_specs=(P(), P()), axis_names={"data"},
                        check_vma=False)
    sm1 = jax.shard_map(per_leaf, mesh=mesh, in_specs=(P(), P()),
                        out_specs=(P(), P()), axis_names={"data"},
                        check_vma=False)
    agg_b, m_b = jax.jit(sm2)(grads, _flatten_resid(layout, resid))
    agg_p, m_p = jax.jit(sm1)(grads, resid)
    for a, b in zip(jax.tree.leaves(agg_p), jax.tree.leaves(agg_b)):
        assert b.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(m_b["comm_bits_dense"]) == float(m_p["comm_bits_dense"])


def test_bucketed_end_to_end_adaptive():
    _run_both(_params(), "allgather",
              density_policy=make_policy("variance"))


def test_bucketed_end_to_end_hierarchical_two_level():
    _run_both(_params(), "hierarchical", mesh_shape=(1, 1, 1),
              axes_names=("pod", "data", "model"), with_r2=True)


def test_bucketed_end_to_end_momentum_correction():
    _run_both(_params(), "allgather", momentum_correction=0.9,
              with_r2=True, codec_dtype=jnp.bfloat16)


# ---------------------------------------------------------------------------
# jaxpr inspection: one collective per wire level, leaf-count independent
# ---------------------------------------------------------------------------


def _trace_collectives(params, strategy, bucketed, mesh,
                       density_policy=None, with_r2=False):
    spec = get_compressor("topk")
    layout = build_layout(params, MSIZE, RATIO, spec,
                          density_policy=density_policy)
    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    grads = jax.tree.map(lambda p: jnp.ones(p.shape), params)
    resid = aggregate.init_residuals(params, MSIZE)
    flat = jnp.zeros((layout.flat_size,))
    r2_tree = resid if with_r2 else None
    r2_flat = flat if with_r2 else None
    config = CompressionConfig(compressor="topk", ratio=RATIO,
                               strategy=strategy, backend="reference",
                               density_policy=density_policy)
    kw = dict(world=1, step=jnp.int32(0) if density_policy else None)

    def body(g, e, *r2s):
        if bucketed:
            res = aggregate.aggregate_bucketed(
                g, e, layout, config, data_axes, "model",
                jax.random.PRNGKey(0), resid2=r2s[0] if r2s else None,
                **kw)
        else:
            res = aggregate.aggregate_compressed(
                g, e, config, data_axes, "model", MSIZE,
                jax.random.PRNGKey(0), resid2=r2s[0] if r2s else None,
                **kw)
        return res.agg

    sm = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(),) * (2 + with_r2), out_specs=P(),
                       axis_names=set(data_axes), check_vma=False)
    args = ((grads, flat) if bucketed else (grads, resid))
    args += ((r2_flat if bucketed else r2_tree,) if with_r2 else ())
    return count_wire_collectives(jax.make_jaxpr(sm)(*args))


def test_jaxpr_one_collective_per_level_independent_of_leaf_count():
    """The ISSUE-5 acceptance check: exactly one data-axis collective per
    wire level per step (log2(W) ppermute rounds total for gTop-k), for
    any leaf count.  One codec pair == 2 array collectives (values +
    indices)."""
    mesh = AbstractMesh((4, MSIZE), ("data", "model"))
    pod_mesh = AbstractMesh((2, 2, MSIZE), ("pod", "data", "model"))
    for params in (_params(), _params(extra=True)):
        L = len(jax.tree.leaves(params))
        # allgather: 1 message (2 array collectives) vs L
        c = _trace_collectives(params, "allgather", True, mesh)
        assert (c["all_gather"], c["ppermute"]) == (2, 0), c
        c = _trace_collectives(params, "allgather", False, mesh)
        assert c["all_gather"] == 2 * L, c
        # gtopk on W=4: log2(4)=2 rounds vs L*2
        c = _trace_collectives(params, "gtopk", True, mesh)
        assert (c["all_gather"], c["ppermute"]) == (0, 4), c
        assert c["messages"] == 2  # == log2(W) rounds
        c = _trace_collectives(params, "gtopk", False, mesh)
        assert c["ppermute"] == 4 * L, c
        # hierarchical on (2,2): one collective per pod level vs 2L
        c = _trace_collectives(params, "hierarchical", True, pod_mesh,
                               with_r2=True)
        assert (c["all_gather"], c["ppermute"]) == (4, 0), c
        c = _trace_collectives(params, "hierarchical", False, pod_mesh,
                               with_r2=True)
        assert c["all_gather"] == 4 * L, c


def test_jaxpr_adaptive_bucketed_still_single_collective():
    mesh = AbstractMesh((4, MSIZE), ("data", "model"))
    c = _trace_collectives(_params(), "allgather", True, mesh,
                           density_policy=make_policy("variance"))
    assert (c["all_gather"], c["ppermute"]) == (2, 0), c


# ---------------------------------------------------------------------------
# train-step integration on the single-device mesh
# ---------------------------------------------------------------------------


def test_train_step_bucketed_matches_per_leaf():
    from repro.optim import constant, sgd_momentum
    from repro.train import init_train_state, make_train_step

    spec = get_compressor("topk")
    params = _params()
    # the single CPU device forces a (1, 1) mesh, so the layout is built
    # at the mesh's model size (1); the multi-shard runs live in the
    # slow job (tests/_dist_check.py bucketed)
    layout = build_layout(params, 1, RATIO, spec)
    mesh = make_mesh((1, 1), ("data", "model"))
    opt = sgd_momentum(0.9)

    def loss_fn(p, b):
        l = sum(jnp.sum((leaf * b["x"][0, 0]) ** 2)
                for leaf in jax.tree.leaves(p))
        return l, {"loss": l}

    batch = {"x": jnp.ones((1, 1))}
    runs = {}
    for label, lay in (("perleaf", None), ("bucketed", layout)):
        state = init_train_state(params, opt, workers=1, model_size=1,
                                 layout=lay)
        if lay is not None:
            assert state["resid"].shape == (1, layout.flat_size)
        step = make_train_step(
            None, mesh, opt, constant(0.1),
            compression=CompressionConfig(compressor="topk", ratio=RATIO),
            loss_fn=loss_fn, layout=lay)
        for _ in range(2):
            state, m = step(state, batch)
        runs[label] = (state, m)
    for a, b in zip(jax.tree.leaves(runs["perleaf"][0]["params"]),
                    jax.tree.leaves(runs["bucketed"][0]["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        pack_residual_arrays(
            layout, [np.asarray(x)[0] for x in
                     jax.tree.leaves(runs["perleaf"][0]["resid"])]),
        np.asarray(runs["bucketed"][0]["resid"])[0])
    assert float(runs["bucketed"][1]["collectives_per_step"]) == 1.0


def test_train_step_chunked_matches_unchunked():
    """--chunks N on the single-device mesh: bit-identical params and
    residuals to chunks=1 over 3 steps, with collectives_per_step = N
    (the multi-shard bit-identity lives in tests/_dist_check.py
    ``chunked``)."""
    from repro.optim import constant, sgd_momentum
    from repro.train import init_train_state, make_train_step

    spec = get_compressor("topk")
    params = _grads(_params(), seed=4)   # nonzero params: real gradients,
    layout = build_layout(params, 1, RATIO, spec)   # non-degenerate top-k
    mesh = make_mesh((1, 1), ("data", "model"))
    opt = sgd_momentum(0.9)

    def loss_fn(p, b):
        l = sum(jnp.sum((leaf * b["x"][0, 0]) ** 2)
                for leaf in jax.tree.leaves(p))
        return l, {"loss": l}

    batch = {"x": jnp.ones((1, 1))}
    runs = {}
    for n in (1, 3):
        state = init_train_state(params, opt, workers=1, model_size=1,
                                 layout=layout)
        step = make_train_step(
            None, mesh, opt, constant(0.1),
            compression=CompressionConfig(compressor="topk", ratio=RATIO,
                                          chunks=n),
            loss_fn=loss_fn, layout=layout)
        for _ in range(3):
            state, m = step(state, batch)
        assert float(m["collectives_per_step"]) == float(n)
        runs[n] = state
    for a, b in zip(jax.tree.leaves(runs[1]["params"]),
                    jax.tree.leaves(runs[3]["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(runs[1]["resid"]),
                                  np.asarray(runs[3]["resid"]))


def test_train_step_chunked_needs_bucketed_pipeline():
    from repro.optim import constant, sgd_momentum
    from repro.train import make_train_step

    params = _params()
    mesh = make_mesh((1, 1), ("data", "model"))
    opt = sgd_momentum(0.9)
    layout = build_layout(params, 1, RATIO, get_compressor("topk"))
    sparse2 = CompressionConfig(compressor="topk", ratio=RATIO, chunks=2)
    with pytest.raises(ValueError):   # chunks without a layout
        make_train_step(None, mesh, opt, constant(0.1), compression=sparse2)
    with pytest.raises(ValueError):   # chunks on the dense path
        make_train_step(None, mesh, opt, constant(0.1),
                        compression=CompressionConfig(compressor="none",
                                                      chunks=2))
    with pytest.raises(ValueError):   # nonsensical chunk count
        make_train_step(None, mesh, opt, constant(0.1),
                        compression=sparse2.replace(chunks=0),
                        layout=layout)


def test_train_step_layout_mismatch_fails_loudly():
    from repro.optim import constant, sgd_momentum
    from repro.train import init_train_state, make_train_step

    params = _params()
    mesh = make_mesh((1, 1), ("data", "model"))
    opt = sgd_momentum(0.9)
    layout1 = build_layout(params, 1, RATIO, get_compressor("topk"))
    topk = CompressionConfig(compressor="topk", ratio=RATIO)
    with pytest.raises(ValueError):   # model size != mesh model axis
        make_train_step(None, mesh, opt, constant(0.1), compression=topk,
                        layout=build_layout(params, 2, RATIO,
                                            get_compressor("topk")))
    with pytest.raises(ValueError):   # compressor mismatch
        make_train_step(None, mesh, opt, constant(0.1),
                        compression=topk.replace(compressor="gaussiank"),
                        layout=layout1)
    with pytest.raises(ValueError):   # ratio mismatch
        make_train_step(None, mesh, opt, constant(0.1),
                        compression=topk.replace(ratio=RATIO * 2),
                        layout=layout1)
    with pytest.raises(ValueError):   # state model size mismatch
        init_train_state(params, opt, workers=1, model_size=4,
                         layout=layout1)
