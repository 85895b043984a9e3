"""Validation of the trip-count-aware HLO cost analyzer against programs
with known FLOP counts (the §Roofline input pipeline), plus the chunked-
schedule structure checks (ISSUE 6): jaxpr collective count x N under
chunking, the backward-pass schedule seam, and the overlap cost model."""
import gzip
import json
import os

import pytest

import jax
import jax.numpy as jnp

from repro.launch.hlo_cost import (analyze, count_schedule_markers,
                                   count_wire_collectives)


def _flops(fn, *args):
    txt = jax.jit(fn).lower(*args).compile().as_text()
    return analyze(txt)


def test_plain_matmul():
    x = jnp.ones((64, 128))
    w = jnp.ones((128, 256))
    r = _flops(lambda x, w: x @ w, x, w)
    expected = 2 * 64 * 128 * 256
    assert abs(r["flops"] - expected) / expected < 0.05


def test_scan_multiplies_trip_count():
    def f(x, ws):
        def body(h, w):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, ws)
        return h.sum()
    x = jnp.ones((128, 256))
    ws = jnp.ones((10, 256, 256))
    r = _flops(f, x, ws)
    expected = 10 * 2 * 128 * 256 * 256
    assert abs(r["flops"] - expected) / expected < 0.02


def test_nested_scans():
    def f2(x, ws):
        def outer_body(h, w):
            def inner(h2, _):
                return jnp.tanh(h2 @ w), None
            h, _ = jax.lax.scan(inner, h, None, length=5)
            return h, None
        h, _ = jax.lax.scan(outer_body, x, ws)
        return h.sum()
    x = jnp.ones((128, 256))
    ws = jnp.ones((10, 256, 256))
    r = _flops(f2, x, ws)
    expected = 50 * 2 * 128 * 256 * 256
    assert abs(r["flops"] - expected) / expected < 0.02


def test_grad_of_scan_counts_backward():
    def f(x, ws):
        def body(h, w):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(jax.checkpoint(body), x, ws)
        return (h ** 2).sum()
    x = jnp.ones((128, 256))
    ws = jnp.ones((10, 256, 256))
    r = _flops(jax.grad(f), x, ws)
    fwd = 10 * 2 * 128 * 256 * 256
    # fwd + backward (2 dots/layer) >= 3x forward
    assert r["flops"] >= 2.9 * fwd


def test_bytes_slicing_not_billed_full():
    """dynamic-slice of a big stacked buffer inside a scan must not bill
    the whole buffer per iteration."""
    big = jnp.ones((64, 1024, 1024))  # 256 MB

    def f(x, ws):
        def body(h, w):
            return h + w[:8, :8].sum(), None
        h, _ = jax.lax.scan(body, x, ws)
        return h
    r = _flops(f, jnp.zeros(()), big)
    # full-billing would be 64 iters x 256MB = 16GB
    assert r["bytes"] < 2e9, r["bytes"]


# ---------------------------------------------------------------------------
# chunked schedule structure (ISSUE 6) — jaxpr-level, AbstractMesh only
# ---------------------------------------------------------------------------


def _params(n_leaves):
    return {f"p{i}": jnp.zeros((60 + 8 * i,)) for i in range(n_leaves)}


def _trace_chunked(params, strategy, n_chunks, world=4):
    from jax.sharding import AbstractMesh
    from jax.sharding import PartitionSpec as P

    from repro.core import get_compressor
    from repro.core.compression import CompressionConfig
    from repro.dist import aggregate
    from repro.dist.layout import build_chunk_plan, build_layout

    spec = get_compressor("topk")
    layout = build_layout(params, 1, 0.05, spec)
    plan = build_chunk_plan(layout, n_chunks)
    grads = jax.tree.map(jnp.zeros_like, params)
    flat = jnp.zeros((layout.flat_size,))
    mesh = AbstractMesh((world, 1), ("data", "model"))
    config = CompressionConfig(compressor="topk", ratio=0.05,
                               strategy=strategy, backend="reference")

    def body(g, e):
        return aggregate.aggregate_bucketed_chunked(
            g, e, layout, plan, config, ("data",), "model",
            jax.random.PRNGKey(0), world=world).agg

    sm = jax.shard_map(body, mesh=mesh, in_specs=(P(), P()),
                       out_specs=P(), axis_names={"data"},
                       check_vma=False)
    return count_wire_collectives(jax.make_jaxpr(sm)(grads, flat))


@pytest.mark.parametrize("strategy,per_msg", [("allgather", (2, 0)),
                                              ("gtopk", (0, 4))])
def test_jaxpr_chunked_collectives_scale_with_n_not_leaves(strategy,
                                                           per_msg):
    """The ISSUE-6 acceptance check: N chunks -> exactly N x the
    per-level wire collectives of the unchunked bucketed pipeline, for
    ANY leaf count (6 vs 9 leaves trace to identical counts — the chunk
    schedule re-dispatches the wire over windows, it never re-introduces
    per-leaf messages)."""
    ag1, pp1 = per_msg
    for n_leaves in (6, 9):
        base = _trace_chunked(_params(n_leaves), strategy, 1)
        assert (base["all_gather"], base["ppermute"]) == (ag1, pp1), base
        for n in (2, 3):
            c = _trace_chunked(_params(n_leaves), strategy, n)
            assert (c["all_gather"], c["ppermute"]) == \
                (n * ag1, n * pp1), (n_leaves, n, c)


def test_backward_seam_emits_one_barrier_per_chunk_group():
    """The custom-vjp schedule seam: the backward pass must carry exactly
    one optimization_barrier per chunk group (the anchor the XLA latency
    scheduler can move collectives across), and the seam must be exact
    identity for the gradients."""
    from repro.core import get_compressor
    from repro.dist.layout import build_chunk_plan, build_layout
    from repro.train.step import _chunk_grad_seam

    params = _params(5)
    layout = build_layout(params, 1, 0.05, get_compressor("topk"))
    leaves = [0.1 * jnp.arange(p.size, dtype=jnp.float32) + 1.0
              for p in jax.tree.leaves(params)]

    def loss_through(seam_fn, ls):
        out = seam_fn(tuple(ls)) if seam_fn else tuple(ls)
        return sum(jnp.sum(x ** 2) for x in out)

    for n in (1, 3, 5):
        plan = build_chunk_plan(layout, n)
        seam = _chunk_grad_seam(plan.groups)
        grad_fn = jax.grad(lambda ls: loss_through(seam, ls))
        jaxpr = jax.make_jaxpr(grad_fn)(leaves)
        assert count_schedule_markers(jaxpr) == plan.n_chunks
        g_seam = grad_fn(leaves)
        g_plain = jax.grad(lambda ls: loss_through(None, ls))(leaves)
        for a, b in zip(g_seam, g_plain):
            assert jnp.array_equal(a, b)


# ---------------------------------------------------------------------------
# overlap cost model (launch/roofline)
# ---------------------------------------------------------------------------


def test_overlapped_collective_time_properties():
    from repro.launch.roofline import overlapped_collective_s

    cases = [(3.0, 1.0), (1.0, 3.0), (2.0, 2.0), (0.0, 5.0), (4.0, 0.0)]
    for c, w in cases:
        serial = overlapped_collective_s(c, w, 1)
        assert serial == c + w                       # N=1 == serial
        prev = serial
        for n in (2, 4, 8, 64):
            t = overlapped_collective_s(c, w, n)
            assert t <= prev + 1e-12, (c, w, n)      # monotone in N
            assert t >= max(c, w) - 1e-12, (c, w, n)  # exposed phase floor
            prev = t
        # the hidden fraction approaches min/(c+w) as N -> inf
        assert overlapped_collective_s(c, w, 10 ** 9) == \
            pytest.approx(max(c, w))


def test_overlap_report_prices_roofline():
    from repro.launch.roofline import overlap_report, roofline_terms

    r = roofline_terms(1e15, 1e12, 1e11, 1e15)
    rep = overlap_report(r, 4)
    compute = max(r.compute_s, r.memory_s)
    assert rep["serial_s"] == pytest.approx(compute + r.collective_s)
    assert rep["overlapped_s"] == pytest.approx(
        max(compute, r.collective_s)
        + min(compute, r.collective_s) / 4)
    assert 0.0 <= rep["hidden_frac"] < 1.0
    assert overlap_report(r, 1)["hidden_frac"] == 0.0


# ---------------------------------------------------------------------------
# alpha-beta wire pricing (ISSUE 9: the alpha * n_messages term)
# ---------------------------------------------------------------------------


def test_roofline_defaults_reproduce_legacy_pricing():
    """With no hw/link/n_messages, roofline_terms must price exactly as
    the old module-global constants did (PEAK_FLOPS/HBM_BW/LINK_BW are
    kept as read-only aliases of the default specs)."""
    from repro.launch import roofline as rl

    r = rl.roofline_terms(1e15, 1e12, 1e11, 1e15)
    assert r.compute_s == pytest.approx(1e15 / rl.PEAK_FLOPS)
    assert r.memory_s == pytest.approx(1e12 / rl.HBM_BW)
    assert r.collective_s == pytest.approx(1e11 / rl.LINK_BW)
    assert r.n_messages == 0.0
    assert r.hardware == rl.DEFAULT_HW.name


def test_roofline_alpha_term_scales_with_messages():
    """collective_s == n_messages * alpha + bytes / beta — the bugfix:
    the old model priced 1000 dispatches and 1 dispatch identically."""
    from repro.launch import roofline as rl
    from repro.launch.topo import LinkSpec

    link = LinkSpec(alpha_s=1e-5, beta_Bps=50e9)
    base = rl.roofline_terms(1e15, 1e12, 1e11, 1e15, link=link)
    many = rl.roofline_terms(1e15, 1e12, 1e11, 1e15, link=link,
                             n_messages=1000)
    assert base.collective_s == pytest.approx(1e11 / 50e9)
    assert many.collective_s - base.collective_s == pytest.approx(1e-2)
    assert many.n_messages == 1000


def test_overlap_chunk_alpha_penalty():
    """Chunking re-pays the dispatch latency per chunk: N chunks add
    (N-1) * chunk_alpha_s, so with a real alpha there is a finite
    optimal N instead of 'more chunks is always better'."""
    from repro.launch.roofline import (overlap_report,
                                      overlapped_collective_s,
                                      roofline_terms)
    from repro.launch.topo import LinkSpec

    t4 = overlapped_collective_s(3.0, 1.0, 4, chunk_alpha_s=0.1)
    assert t4 == pytest.approx(3.0 + 1.0 / 4 + 3 * 0.1)
    # alpha-free monotonicity breaks once alpha is real: huge N loses
    assert overlapped_collective_s(3.0, 1.0, 64, chunk_alpha_s=0.1) > \
        overlapped_collective_s(3.0, 1.0, 4, chunk_alpha_s=0.1)

    link = LinkSpec(alpha_s=1e-3, beta_Bps=50e9)
    r = roofline_terms(1e15, 1e12, 1e11, 1e15, link=link, n_messages=2)
    rep = overlap_report(r, 4, link=link)
    compute = max(r.compute_s, r.memory_s)
    assert rep["overlapped_s"] == pytest.approx(
        max(compute, r.collective_s)
        + min(compute, r.collective_s) / 4 + 3 * 2 * 1e-3)


# ---------------------------------------------------------------------------
# collective_bytes/_messages parser vs recorded wire-stage HLO (ISSUE 9:
# the collective-permute / -start tuple / iota replica_groups bugfixes)
# ---------------------------------------------------------------------------


FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")

_WIRE_FIXTURES = ["wire_allgather_4x2", "wire_gtopk_4x2",
                  "wire_hierarchical_2x2x2", "wire_hier_gtopk_2x2x2"]


def _load_fixture(name):
    with gzip.open(os.path.join(FIXTURES, name + ".hlo.gz"), "rt") as f:
        hlo = f.read()
    with open(os.path.join(FIXTURES, name + ".json")) as f:
        meta = json.load(f)
    return hlo, meta


@pytest.mark.parametrize("name", _WIRE_FIXTURES)
def test_collective_bytes_match_layout_ground_truth(name):
    """Parsed per-device wire bytes of a compiled wire stage must equal
    the layout closed form: collective_count(strategy) events, each
    moving one codec pair (pair_bits/8 bytes).  This is what the
    collective-permute raw-result-bytes counting has to get right — a
    gtopk round's ppermute moves its result ONCE (no group division,
    no group multiplication)."""
    from repro.dist.layout import collective_count
    from repro.launch.roofline import collective_bytes

    hlo, meta = _load_fixture(name)
    got = collective_bytes(hlo)
    events = collective_count(meta["strategy"], meta["world"],
                              meta["n_pods"])
    expected = events * meta["pair_bits"] / 8
    assert got["total"] == expected, (name, got, expected)
    # op-class split: gathers for gather levels, permutes for rounds
    ag = got.get("all-gather", 0.0)
    cp = got.get("collective-permute", 0.0)
    pair = meta["pair_bits"] / 8
    if meta["strategy"] == "allgather":
        assert (ag, cp) == (pair, 0.0)
    elif meta["strategy"] == "gtopk":
        assert (ag, cp) == (0.0, events * pair)
    elif meta["strategy"] == "hierarchical":
        assert (ag, cp) == (2 * pair, 0.0)
    else:  # hier_gtopk: one inner gather + log2(P) outer rounds
        assert (ag, cp) == (pair, (events - 1) * pair)


@pytest.mark.parametrize("name", _WIRE_FIXTURES)
def test_collective_messages_match_dispatch_model(name):
    """Parsed dispatch counts must equal MSGS_PER_PAIR x the layout's
    collective_count — each codec-pair event is two array messages
    (values + indices), exactly the alpha-term multiplier the tuner
    uses."""
    from repro.dist.layout import collective_count
    from repro.dist.tuner import MSGS_PER_PAIR
    from repro.launch.roofline import collective_messages

    hlo, meta = _load_fixture(name)
    got = collective_messages(hlo)
    events = collective_count(meta["strategy"], meta["world"],
                              meta["n_pods"])
    assert got["total"] == MSGS_PER_PAIR * events, (name, got, events)


def test_async_start_tuple_counts_result_once():
    """-start ops return (operand, result[, context]) tuples; the parser
    must bill the result once, not the whole tuple (which double-counts
    the payload), and must skip the -done half entirely."""
    from repro.launch.roofline import collective_bytes, collective_messages

    hlo = """
  %ag = (f32[1,64]{1,0}, f32[4,64]{1,0}) all-gather-start(f32[1,64]{1,0} %p0), replica_groups={{0,1,2,3}}, dimensions={0}
  %agd = f32[4,64]{1,0} all-gather-done((f32[1,64]{1,0}, f32[4,64]{1,0}) %ag)
  %cp = (f32[64]{0}, f32[64]{0}, u32[], u32[]) collective-permute-start(f32[64]{0} %p1), source_target_pairs={{0,1},{1,0}}
  %cpd = f32[64]{0} collective-permute-done((f32[64]{0}, f32[64]{0}, u32[], u32[]) %cp)
"""
    got = collective_bytes(hlo)
    # all-gather: result 4*64*4 bytes / group 4 == contributed shard
    assert got["all-gather"] == 4 * 64 * 4 / 4
    # collective-permute: the 64-element result once — NOT the tuple sum
    assert got["collective-permute"] == 64 * 4
    msgs = collective_messages(hlo)
    assert msgs == {"all-gather": 1.0, "collective-permute": 1.0,
                    "total": 2.0}


def test_iota_replica_groups_all_arities():
    """replica_groups=[G,S]<=[dims...] — the iota form's dims list may
    have any arity (and a transpose tail); only the leading [groups,
    group_size] is structural.  The old 2-field-only regex silently fell
    back to group_size=1, inflating all-gather bytes by the group
    factor."""
    from repro.launch.roofline import collective_bytes

    base = "%ag = f32[8,32]{1,0} all-gather(f32[1,32]{1,0} %x), " \
        "dimensions={0}, replica_groups="
    for form in ("[1,8]<=[8]", "[1,8]<=[2,4]T(1,0)", "[1,8]<=[2,2,2]T(0,2,1)"):
        got = collective_bytes(base + form + "\n")
        assert got["all-gather"] == 8 * 32 * 4 / 8, form
