"""Compressor-conformance property suite (ISSUE 7 tentpole).

Every spec registered in ``compressors.available()`` rides the same
stack — sentinel codec, error feedback, bucketed wire, chunked schedule
— so every spec must obey the same contracts.  This suite pins them,
parameterized over the whole registry, so the next compressor anyone
adds gets its contracts checked for free:

1. **codec contract of the selector output**: static ``(k_cap,)``
   shapes, sentinel slots carry value 0, real indices are in-range and
   duplicate-free, real values equal ``u`` at their indices;
2. **Eq.-2 mass conservation through error feedback**:
   ``decode(values, indices) + e' == g + e``;
3. **wire roundtrip with offsets/sentinels**: ``offset_indices`` +
   decode into a wider bucket window is mass-identical to the leaf-local
   decode (the bucket pipeline's index transform);
4. **fused == reference bit-equality** where a fused pipeline exists;
5. **bucketed == per-leaf == chunked equivalence** at the compression
   layer (same values/indices/residuals for the same leaves — the wire-
   level equivalence on a real mesh is pinned by tests/_dist_check.py);
6. **delta-stream roundtrip** (DESIGN.md §13): every spec can carry the
   train-to-serve weight-delta stream — resync publishes make the
   replica BIT-equal to the trainer, the published view always equals
   the packed replica bitwise, and ``pub + resid`` conserves the params
   through the publisher's error feedback;

plus the adaptive-path contracts: allocation budget exactness per spec,
dynamic-k selection honoring the traced budget, and the global-k
controller's scale law (``core/adaptk.global_scale``, DESIGN.md §12).

Coverage is an explicit opt-in: a spec must be listed in
``CONFORMANCE`` (or carry a ``WAIVERS`` entry with a reason) —
``test_registry_guard_every_spec_covered`` fails loudly otherwise.

Runs under real ``hypothesis`` (CI ``properties`` job, pinned
``--hypothesis-seed``) and under the deterministic conftest stub; the
strategies used here (``integers`` / ``sampled_from`` / ``floats`` /
``tuples``) are exactly the stub's slice.  Geometry is drawn from a
fixed table so jit caches stay warm across examples.
"""
import numpy as np

import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import adaptk, codec, compressors
from repro.core.compression import CompressionConfig
from repro.core.compressors import get_compressor
from repro.core.error_feedback import compress_with_ef, supports_fused
from repro.dist import aggregate
from repro.dist.layout import (build_chunk_plan, build_layout, chunk_view,
                               leaf_key_salt, pack_grads)
from repro.serve import (DELTA, RESYNC, apply_message, init_publisher_state,
                         message_bits, publish)

ALL = tuple(compressors.available())

# the opt-in coverage registry: every spec here runs every generic
# contract below.  New specs must be added here (usually nothing else is
# needed — the contracts are generic) or waived with a reason.
CONFORMANCE = frozenset({
    "topk", "randk", "gaussiank", "gaussiank2", "dgck", "trimmedk",
    "histk", "rtopk",
})
# name -> reason a registered spec cannot ride the shared stack
WAIVERS: dict = {}

COVERED = st.sampled_from(sorted(CONFORMANCE - set(WAIVERS)))
SEEDS = st.integers(0, 2**31 - 1)
# fixed geometry table (static shapes keep jit caches warm), including
# the k == 1 and k == d corners
GEOMS = ((16, 1), (33, 4), (96, 96), (257, 5), (1024, 48))
GEOM = st.sampled_from(GEOMS)
# width=32 bounds must be float32-representable: 1e-3 is not, so the
# lower bound is its nearest float32
SCALES = st.floats(min_value=float(np.float32(1e-3)), max_value=1e3,
                   width=32, allow_nan=False, allow_infinity=False)


def _key_for(spec, seed):
    return jax.random.PRNGKey(seed & 0xFFFF) if spec.needs_key else None


def _u(seed, d, scale=1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray((scale * rng.normal(size=d)).astype(np.float32))


# ---------------------------------------------------------------------------
# registry guard (satellite: new specs must opt in or be waived)
# ---------------------------------------------------------------------------


def test_registry_guard_every_spec_covered():
    missing = [n for n in ALL if n not in CONFORMANCE and n not in WAIVERS]
    assert not missing, (
        f"compressor spec(s) {missing} are registered in "
        "compressors.available() but have NO conformance coverage.  Add "
        "them to CONFORMANCE in tests/test_compressor_conformance.py — "
        "the contracts are generic, so listing the name is usually all "
        "that is needed — or record an explicit WAIVERS entry explaining "
        "why the spec cannot obey the shared codec/EF/bucket contracts.")
    stale = sorted((CONFORMANCE | set(WAIVERS)) - set(ALL))
    assert not stale, (
        f"conformance entries {stale} name specs that are no longer "
        "registered; prune them from CONFORMANCE/WAIVERS")
    double = sorted(CONFORMANCE & set(WAIVERS))
    assert not double, (
        f"spec(s) {double} are both covered and waived; pick one")


# ---------------------------------------------------------------------------
# contract 1: selector output obeys the sentinel-codec contract
# ---------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(COVERED, SEEDS, GEOM)
def test_select_codec_contract(name, seed, geom):
    d, k = geom
    spec = get_compressor(name)
    k = min(k, d)
    u = _u(seed, d)
    k_cap = spec.k_cap(k, d)
    assert 0 < k_cap <= d, (name, k, d, k_cap)
    v, i = spec.select(u, k, _key_for(spec, seed))
    assert v.shape == (k_cap,) and i.shape == (k_cap,), (name, geom)
    iv, vv = np.asarray(i), np.asarray(v)
    real = iv != codec.SENTINEL
    assert np.all(vv[~real] == 0.0), f"{name}: sentinel slot with mass"
    assert np.all((iv[real] >= 0) & (iv[real] < d)), f"{name}: oob index"
    ridx = iv[real]
    assert len(np.unique(ridx)) == len(ridx), f"{name}: duplicate index"
    np.testing.assert_array_equal(
        vv[real], np.asarray(u)[ridx],
        err_msg=f"{name}: values must equal u at their indices")


# ---------------------------------------------------------------------------
# contract 2: Eq.-2 conservation through error feedback
# ---------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(COVERED, SEEDS, GEOM, SCALES)
def test_ef_conservation(name, seed, geom, scale):
    d, k = geom
    spec = get_compressor(name)
    k = min(k, d)
    rng = np.random.default_rng(seed)
    g = jnp.asarray((scale * rng.normal(size=d)).astype(np.float32))
    e = jnp.asarray((scale * 0.3 * rng.normal(size=d)).astype(np.float32))
    vals, idx, e2 = compress_with_ef(g, spec, k, key=_key_for(spec, seed),
                                     e=e, backend="reference")
    dec = codec.decode(vals.astype(jnp.float32), idx, d)
    np.testing.assert_allclose(np.asarray(dec + e2), np.asarray(g + e),
                               rtol=1e-5, atol=1e-5 * scale,
                               err_msg=f"{name}: Eq.-2 mass not conserved")


# ---------------------------------------------------------------------------
# contract 3: wire roundtrip — bucket-offset indices decode identically
# ---------------------------------------------------------------------------


@settings(max_examples=40, deadline=None)
@given(COVERED, SEEDS, GEOM, st.integers(0, 37))
def test_wire_offset_roundtrip(name, seed, geom, off):
    """``offset_indices`` + decode into a wider window (the bucket
    pipeline's index transform) is mass-identical to the local decode,
    sentinels stay sentinels, and nnz is preserved."""
    d, k = geom
    spec = get_compressor(name)
    k = min(k, d)
    u = _u(seed, d)
    v, i = spec.select(u, k, _key_for(spec, seed))
    gi = codec.offset_indices(i, off)
    assert int(codec.nnz(gi)) == int(codec.nnz(i))
    wide = codec.decode(v.astype(jnp.float32), gi, off + d + 11)
    local = codec.decode(v.astype(jnp.float32), i, d)
    np.testing.assert_array_equal(np.asarray(wide[off:off + d]),
                                  np.asarray(local))
    assert float(jnp.sum(jnp.abs(wide[:off]))) == 0.0
    assert float(jnp.sum(jnp.abs(wide[off + d:]))) == 0.0


# ---------------------------------------------------------------------------
# contract 4: fused == reference, bit-exact, where a fused path exists
# ---------------------------------------------------------------------------

FUSED = tuple(n for n in sorted(CONFORMANCE)
              if supports_fused(get_compressor(n)))


@settings(max_examples=25, deadline=None)
@given(st.sampled_from(FUSED), SEEDS, GEOM)
def test_fused_matches_reference_bitwise(name, seed, geom):
    d, k = geom
    spec = get_compressor(name)
    k = min(k, d)
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(size=d).astype(np.float32))
    e = jnp.asarray((0.3 * rng.normal(size=d)).astype(np.float32))
    fv, fi, fe = compress_with_ef(g, spec, k, e=e, backend="fused")
    rv, ri, re = compress_with_ef(g, spec, k, e=e, backend="reference")
    np.testing.assert_array_equal(np.asarray(fi), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(fv), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(fe), np.asarray(re))


# ---------------------------------------------------------------------------
# contract 5: bucketed == per-leaf == chunked (compression layer)
# ---------------------------------------------------------------------------


@settings(max_examples=12, deadline=None)
@given(COVERED, SEEDS)
def test_granularity_equivalence(name, seed):
    """The three dispatch granularities run the SAME selection per leaf:
    per-leaf ``compress_worker``, the packed ``bucket_compress``, and
    ``bucket_compress`` over ``chunk_view`` windows must produce
    identical values, (offset-adjusted) indices and residuals."""
    spec = get_compressor(name)
    M, ratio = 2, 0.08
    rng = np.random.default_rng(seed)
    shapes = {"wa": (40, 3), "wb": (17,), "wc": (9, 5)}
    params = {n: jnp.zeros(s, jnp.float32) for n, s in shapes.items()}
    grads = {n: jnp.asarray(rng.normal(size=s).astype(np.float32))
             for n, s in shapes.items()}
    layout = build_layout(params, M, ratio, spec)
    resid = {s.name: jnp.asarray(
        (0.2 * rng.normal(size=s.d_pad)).astype(np.float32))
        for s in layout.segments}
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)

    # per-leaf oracle
    per_leaf = {}
    for s in layout.segments:
        lkey = jax.random.fold_in(key, leaf_key_salt(s.name))
        v, i, ne, _ = aggregate.compress_worker(
            grads[s.name], resid[s.name], spec, ratio, M, lkey,
            backend="reference")
        per_leaf[s.name] = (v, i, ne)

    # bucketed
    G = pack_grads(layout, grads, jnp.float32)
    E = jnp.concatenate([resid[s.name].reshape(M, s.d_row)
                         for s in layout.segments], axis=1)
    bv, bi, bE, _ = aggregate.bucket_compress(G, E, layout, spec, key,
                                              backend="reference")
    for s in layout.segments:
        v, i, ne = per_leaf[s.name]
        sl = slice(s.cap_off, s.cap_off + s.k_cap)
        np.testing.assert_array_equal(np.asarray(bv[:, sl]), np.asarray(v))
        np.testing.assert_array_equal(
            np.asarray(bi[:, sl]), np.asarray(codec.offset_indices(
                i, s.row_off)))
        rl = slice(s.row_off, s.row_off + s.d_row)
        np.testing.assert_array_equal(
            np.asarray(bE[:, rl]), np.asarray(ne.reshape(M, s.d_row)))

    # chunked: same bucket compression over chunk_view windows
    plan = build_chunk_plan(layout, 2)
    cvs, cis, cEs = [], [], []
    for grp in plan.groups:
        view = chunk_view(layout, grp)
        Gc = G[:, grp.row_off:grp.row_off + grp.d_row]
        Ec = E[:, grp.row_off:grp.row_off + grp.d_row]
        v, i, ne, _ = aggregate.bucket_compress(Gc, Ec, view, spec, key,
                                                backend="reference")
        cvs.append(v)
        cis.append(codec.offset_indices(i, grp.row_off))
        cEs.append(ne)
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate(cvs, axis=1)), np.asarray(bv))
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate(cis, axis=1)), np.asarray(bi))
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate(cEs, axis=1)), np.asarray(bE))


# ---------------------------------------------------------------------------
# contract 6: delta-stream publish/subscribe roundtrip (DESIGN.md §13)
# ---------------------------------------------------------------------------


@settings(max_examples=12, deadline=None)
@given(COVERED, SEEDS)
def test_delta_stream_roundtrip(name, seed):
    """Every covered spec can carry the train-to-serve weight-delta
    stream: the first publish (seq 0) and every ``resync_every``-th one
    resync the replica BIT-equal to the trainer; delta publishes keep
    ``pub == pack(replica)`` bitwise (publisher and subscriber apply the
    same ``decode_add``), conserve params through the publisher EF
    (``pub + resid == P`` up to float addition), and cost exactly the
    layout's codec-pair bits on the wire."""
    spec = get_compressor(name)
    M, ratio, resync_every = 2, 0.08, 3
    rng = np.random.default_rng(seed)
    shapes = {"wa": (40, 3), "wb": (17,), "wc": (9, 5)}
    params = {n: jnp.asarray(rng.normal(size=s).astype(np.float32))
              for n, s in shapes.items()}
    layout = build_layout(params, M, ratio, spec)
    config = CompressionConfig(compressor=name, ratio=ratio,
                               backend="reference")
    state = init_publisher_state(layout)
    replica = {n: jnp.zeros(s, jnp.float32) for n, s in shapes.items()}
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)

    for tick in range(5):
        params = {n: p + jnp.asarray(
            (0.01 * rng.normal(size=p.shape)).astype(np.float32))
            for n, p in params.items()}
        state, msg = publish(state, params, layout, config, key,
                             resync_every=resync_every)
        assert msg.seq == tick
        if tick == 0 or tick % resync_every == 0:
            assert msg.kind == RESYNC
            assert message_bits(msg) == layout.model_size * \
                layout.d_row_total * 32
        else:
            assert msg.kind == DELTA
            assert message_bits(msg) == layout.pair_bits(None)
        replica = apply_message(replica, layout, msg)
        if msg.kind == RESYNC:
            for n in shapes:
                np.testing.assert_array_equal(
                    np.asarray(replica[n]), np.asarray(params[n]),
                    err_msg=f"{name}: replica != trainer at resync")
        # the published view IS the packed replica, bitwise, every tick
        R = pack_grads(layout, replica, jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(state["pub"]), np.asarray(R),
            err_msg=f"{name}: pub != pack(replica)")
        # publisher EF conserves params: pub + resid == P
        Pb = pack_grads(layout, params, jnp.float32)
        np.testing.assert_allclose(
            np.asarray(state["pub"] + state["resid"]), np.asarray(Pb),
            rtol=1e-5, atol=1e-5,
            err_msg=f"{name}: pub + resid does not conserve params")


# ---------------------------------------------------------------------------
# adaptive contracts: budget exactness, dynamic-k, global-k controller
# ---------------------------------------------------------------------------

DYNAMIC = st.sampled_from(sorted(adaptk.DYNAMIC_COMPRESSORS))
# exact-k dynamic selectors: rank at capacity, mask ranks >= k — the
# budget is honored EXACTLY; threshold-style selectors approximate it
EXACT_DYNAMIC = ("topk", "randk", "rtopk")


@settings(max_examples=40, deadline=None)
@given(DYNAMIC, SEEDS, st.integers(1, 4000))
def test_dynamic_budget_allocation_and_selection(name, seed, K_req):
    spec = get_compressor(name)
    dims = (1024, 257, 96)
    pol = adaptk.make_policy("variance")
    lo, hi = zip(*(adaptk.leaf_bounds(d, 0.05, pol) for d in dims))
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.uniform(0.0, 1.0, size=len(dims)).astype(
        np.float32))
    k_alloc, K_eff = adaptk.allocate(jnp.int32(K_req), w, lo, hi)
    ka = np.asarray(k_alloc)
    assert int(np.sum(ka)) == int(K_eff), "allocation not budget-exact"
    assert int(K_eff) == int(np.clip(K_req, sum(lo), sum(hi)))
    assert np.all(ka >= np.asarray(lo)) and np.all(ka <= np.asarray(hi))

    # dynamic selection on one leaf honors the traced budget under the
    # static capacity, conserving mass
    d, k_cap = dims[0], int(hi[0])
    u = _u(seed, d)
    k = jnp.int32(int(ka[0]))
    v, i = adaptk.select_dynamic(spec, u, k, k_cap,
                                 _key_for(spec, seed)
                                 if spec.needs_key else None)
    assert v.shape == (min(k_cap, d),) and i.shape == v.shape
    nnz = int(codec.nnz(i))
    assert nnz <= min(k_cap, d)
    if name in EXACT_DYNAMIC:
        assert nnz == int(ka[0]), f"{name}: dynamic budget not exact"
    iv = np.asarray(i)
    ridx = iv[iv != codec.SENTINEL]
    assert len(np.unique(ridx)) == len(ridx), f"{name}: duplicate index"
    dec = codec.decode(v.astype(jnp.float32), i, d)
    resid = u - dec
    np.testing.assert_allclose(np.asarray(dec + resid), np.asarray(u),
                               rtol=1e-6, atol=1e-6)


@settings(max_examples=40, deadline=None)
@given(SEEDS, st.tuples(st.floats(min_value=0.0, max_value=0.98,
                                  allow_nan=False, allow_infinity=False),
                        st.floats(min_value=0.05, max_value=1.0,
                                  allow_nan=False, allow_infinity=False)))
def test_global_scale_contract(seed, ema_floor):
    """The norm-decay controller's scale law (DESIGN.md §12): seeds to
    exactly 1 on first observation, always inside [global_floor, 1],
    gnorm0 frozen after seeding, and zero observations never poison the
    state (self-seeding keeps waiting for the first positive norm)."""
    gema, gfloor = ema_floor
    pol = adaptk.make_policy("variance", global_policy="normdecay",
                             global_ema=gema, global_floor=gfloor)
    state = adaptk.init_controller_state(3, global_k=True)
    rng = np.random.default_rng(seed)

    # zero observations: state stays unseeded, scale stays 1
    s, upd = adaptk.global_scale(state, jnp.float32(0.0), pol)
    state = {**state, **upd}
    assert float(s) == 1.0 and float(state["gnorm0"]) == 0.0

    first = float(rng.uniform(0.5, 50.0))
    s, upd = adaptk.global_scale(state, jnp.float32(first), pol)
    state = {**state, **upd}
    assert abs(float(s) - 1.0) < 1e-6, "first observation must scale 1"
    assert abs(float(state["gnorm0"]) - first) < 1e-5

    for _ in range(8):
        obs = float(rng.uniform(0.0, 2.0) * first)
        s, upd = adaptk.global_scale(state, jnp.float32(obs), pol)
        state = {**state, **upd}
        assert gfloor - 1e-6 <= float(s) <= 1.0 + 1e-6
        assert abs(float(state["gnorm0"]) - first) < 1e-5, "ref drifted"

    # stateless non-globalk call is the identity
    s0, upd0 = adaptk.global_scale(None, 123.0,
                                   adaptk.make_policy("variance"))
    assert float(s0) == 1.0 and upd0 == {}


def test_global_scale_requires_controller_state():
    pol = adaptk.make_policy("variance", global_policy="normdecay")
    try:
        adaptk.global_scale(None, 1.0, pol)
    except ValueError as err:
        assert "init_controller_state" in str(err)
    else:
        raise AssertionError("global_scale must reject missing state")


def test_globalk_allocation_single_device():
    """The shared adaptive-allocation phase with the controller enabled,
    end to end on a 1-device mesh: the budget shrinks with the observed
    norm decay, never below the floor, the per-leaf split stays
    budget-exact, and the controller scalars round-trip through the
    state dict."""
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    pol = adaptk.make_policy("uniform", global_policy="normdecay",
                             global_ema=0.0, global_floor=0.5)
    dims, ratio = (400, 120), 0.1
    lo, hi = zip(*(adaptk.leaf_bounds(d, ratio, pol) for d in dims))

    def body(state, sigs, sqs):
        return aggregate._adaptive_allocation(
            state, [sigs[0], sigs[1]], [sqs[0], sqs[1]], dims, ratio,
            pol, jnp.int32(0), lo, hi, ("data",))

    run = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P()), out_specs=(P(), P(), P()),
        axis_names={"data"}, check_vma=False))

    state = adaptk.init_controller_state(len(dims), global_k=True)
    sigs = jnp.asarray([4.0, 1.2], jnp.float32)

    k1, K1, state = run(state, sigs, jnp.asarray([9.0, 16.0], jnp.float32))
    assert int(jnp.sum(k1)) == int(K1)
    assert int(K1) == 52  # round(0.1 * 520), first observation: scale 1

    # norm decays 4x -> scale sqrt(1/4) = 0.5 (ema 0 tracks instantly)
    k2, K2, state = run(state, sigs, jnp.asarray([2.25, 4.0], jnp.float32))
    assert int(jnp.sum(k2)) == int(K2)
    assert int(K2) == 26  # round(52 * 0.5), above sum(lo)
    assert float(state["gnorm0"]) == 25.0
    assert "signal" in state and "count" in state
