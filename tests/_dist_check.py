"""Subprocess body for tests/test_distributed.py (8 host devices)."""
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec, get_compressor
from repro.core.compression import CompressionConfig
from repro.launch.mesh import data_world_size, make_mesh, model_axis_size
from repro.models import ModelConfig, init_params, loss_fn
from repro.optim import constant, sgd_momentum
from repro.train import init_train_state, make_train_step

CFG = ModelConfig(name="t", arch_type="dense", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, d_ff=128,
                  vocab_size=64).validate()


def _batch(seed=1, B=8, S=16):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (B, S), 0,
                              CFG.vocab_size)
    return {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}


def check_eq2():
    """Distributed TopK-SGD on a (4,2) mesh must match a single-process
    simulation of Eq. (2): per-worker local top-k over each model-shard row,
    all-gather, average, SGD-momentum update."""
    mesh = make_mesh((4, 2), ("data", "model"))
    W = data_world_size(mesh)
    msize = model_axis_size(mesh)
    opt = sgd_momentum(0.9)
    ratio, lr, steps = 0.02, 0.05, 3

    params = init_params(CFG, jax.random.PRNGKey(0))
    state = init_train_state(params, opt, workers=W, model_size=msize)
    step = make_train_step(
        CFG, mesh, opt, constant(lr), remat=False,
        compression=CompressionConfig(compressor="topk", ratio=ratio))
    batch = _batch()
    for _ in range(steps):
        state, m = step(state, batch)

    # ---- single-process simulation ----
    import math
    spec = get_compressor("topk")
    p_sim = jax.tree.map(jnp.asarray, params)
    mom = jax.tree.map(jnp.zeros_like, params)
    resid = jax.tree.map(
        lambda p: jnp.zeros((W, -(-p.size // msize) * msize)), params)
    grad_fn = jax.jit(jax.grad(
        lambda p, b: loss_fn(p, CFG, b, remat=False)[0]))
    for _ in range(steps):
        # per-worker grads on batch shards
        worker_grads = []
        for w in range(W):
            shard = jax.tree.map(lambda x: x[w * 2:(w + 1) * 2], batch)
            worker_grads.append(grad_fn(p_sim, shard))
        # compressed aggregation per leaf
        leaves, treedef = jax.tree.flatten(p_sim)
        g_leaves = [treedef.flatten_up_to(g) for g in worker_grads]
        e_leaves = treedef.flatten_up_to(resid)
        agg, new_e = [], []
        for li in range(len(leaves)):
            d = leaves[li].size
            d_pad = -(-d // msize) * msize
            d_row = d_pad // msize
            k = max(1, math.ceil(ratio * d))
            k_row = max(1, -(-k // msize))
            dense = jnp.zeros((d_pad,))
            e_new_rows = []
            for w in range(W):
                u = e_leaves[li][w] + jnp.pad(
                    g_leaves[w][li].reshape(-1), (0, d_pad - d))
                u2 = u.reshape(msize, d_row)
                rows_dense, rows_e = [], []
                for r in range(msize):
                    v, i = spec.select(u2[r], k_row, None)
                    dec = codec.decode(v, i, d_row)
                    rows_dense.append(dec)
                    rows_e.append(u2[r] - dec)
                dense = dense + jnp.stack(rows_dense).reshape(-1)
                e_new_rows.append(jnp.stack(rows_e).reshape(-1))
            agg.append((dense / W)[:d].reshape(leaves[li].shape))
            new_e.append(jnp.stack(e_new_rows))
        agg = treedef.unflatten(agg)
        resid = treedef.unflatten(new_e)
        mom = jax.tree.map(lambda m, g: 0.9 * m + g, mom, agg)
        p_sim = jax.tree.map(lambda p, m: p - lr * m, p_sim, mom)

    err = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))),
        state["params"], p_sim)))
    assert err < 2e-5, f"max param deviation {err}"
    print("EQ2 OK", err)


def check_gtopk():
    """gTop-k strategy on a (4,2) mesh vs the single-process simulation.

    Two layers of evidence:
      1. one aggregation call inside shard_map == ``gtopk_simulate`` on
         the same per-worker inputs, within 1e-6 (the merge plumbing —
         ppermute rounds, drop crediting — is bit-identical in exact
         arithmetic, so this is really float-reassociation headroom);
      2. a 3-step TopK-SGD training run matches the simulated update
         loop end-to-end within 1e-6 (identical op order makes even the
         mesh-vs-host grad noise vanish here; observed ~1e-8).
    """
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.dist import aggregate

    mesh = make_mesh((4, 2), ("data", "model"))
    W = data_world_size(mesh)
    msize = model_axis_size(mesh)
    spec = get_compressor("topk")
    ratio, d = 0.02, 407
    d_pad, d_row = aggregate.flat_dims(d, msize)
    _, _, _, k_cap = aggregate.leaf_plan(d, msize, ratio, spec)
    g = jnp.stack([0.01 * jax.random.normal(jax.random.PRNGKey(w), (d,))
                   for w in range(W)])
    e = 0.001 * jax.random.normal(jax.random.PRNGKey(99), (W, d_pad))

    config = CompressionConfig(compressor="topk", ratio=ratio,
                               strategy="gtopk")

    def body(g_loc, e_loc):
        res = aggregate.aggregate_compressed(
            {"w": g_loc[0]}, {"w": e_loc[0]}, config, ("data",),
            "model", msize, jax.random.PRNGKey(7), world=W)
        return res.agg["w"], res.resid["w"][None], res.metrics

    sm = jax.shard_map(body, mesh=mesh,
                       in_specs=(P("data"), P("data")),
                       out_specs=(P(), P("data"), P()),
                       axis_names={"data"}, check_vma=False)
    agg_mesh, new_e_mesh, metrics = jax.jit(sm)(g, e)

    outs = [aggregate.compress_worker(g[w], e[w], spec, ratio, msize, None)
            for w in range(W)]
    partials = [jax.vmap(lambda v, i: codec.decode(v, i, d_row))(o[0], o[1])
                for o in outs]
    final, drops = aggregate.gtopk_simulate(partials, k_cap)
    agg_err = float(jnp.max(jnp.abs(agg_mesh - (final.reshape(-1) / W)[:d])))
    e_sim = jnp.stack([outs[w][2] + drops[w].reshape(-1) for w in range(W)])
    e_err = float(jnp.max(jnp.abs(new_e_mesh - e_sim)))
    assert agg_err < 1e-6, f"aggregation deviation {agg_err}"
    assert e_err < 1e-6, f"residual deviation {e_err}"
    # conservation across the mesh: sum_w u_w == W*mean + sum_w e'_w
    u_sum = jnp.sum(e + jnp.pad(g, ((0, 0), (0, d_pad - d))), axis=0)
    cons = float(jnp.max(jnp.abs(
        u_sum - jnp.pad(agg_mesh * W, (0, d_pad - d))
        - jnp.sum(new_e_mesh, axis=0))))
    assert cons < 1e-6, f"conservation violation {cons}"
    # O(log W) vs O(W) wire pairs at equal k_cap
    pair_bits = msize * k_cap * 64
    assert float(metrics["comm_bits_sparse"]) == 2 * pair_bits  # log2(4)
    assert 2 * pair_bits < W * pair_bits

    # ---- end-to-end training vs simulated update loop ----
    opt = sgd_momentum(0.9)
    lr, steps = 0.05, 3
    params = init_params(CFG, jax.random.PRNGKey(0))
    state = init_train_state(params, opt, workers=W, model_size=msize,
                             compression=config)
    step = make_train_step(CFG, mesh, opt, constant(lr), remat=False,
                           compression=config)
    batch = _batch()
    for _ in range(steps):
        state, m = step(state, batch)

    spec = get_compressor("topk")
    p_sim = jax.tree.map(jnp.asarray, params)
    mom = jax.tree.map(jnp.zeros_like, params)
    resid = jax.tree.map(
        lambda p: jnp.zeros((W, -(-p.size // msize) * msize)), params)
    grad_fn = jax.jit(jax.grad(
        lambda p, b: loss_fn(p, CFG, b, remat=False)[0]))
    for _ in range(steps):
        worker_grads = [grad_fn(p_sim, jax.tree.map(
            lambda x: x[w * 2:(w + 1) * 2], batch)) for w in range(W)]
        leaves, treedef = jax.tree.flatten(p_sim)
        g_leaves = [treedef.flatten_up_to(gw) for gw in worker_grads]
        e_leaves = treedef.flatten_up_to(resid)
        agg, new_e = [], []
        for li in range(len(leaves)):
            dl = leaves[li].size
            d_pad, d_row = aggregate.flat_dims(dl, msize)
            _, _, _, k_cap = aggregate.leaf_plan(dl, msize, ratio, spec)
            outs = [aggregate.compress_worker(
                g_leaves[w][li], e_leaves[li][w], spec, ratio, msize, None)
                for w in range(W)]
            partials = [jax.vmap(
                lambda v, i: codec.decode(v, i, d_row))(o[0], o[1])
                for o in outs]
            final, drops = aggregate.gtopk_simulate(partials, k_cap)
            agg.append((final.reshape(-1) / W)[:dl].reshape(
                leaves[li].shape))
            new_e.append(jnp.stack(
                [outs[w][2] + drops[w].reshape(-1) for w in range(W)]))
        agg = treedef.unflatten(agg)
        resid = treedef.unflatten(new_e)
        mom = jax.tree.map(lambda m, g: 0.9 * m + g, mom, agg)
        p_sim = jax.tree.map(lambda p, m: p - lr * m, p_sim, mom)

    err = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))),
        state["params"], p_sim)))
    assert err < 1e-6, f"max param deviation {err}"
    print("GTOPK OK", agg_err, err)


def check_dense():
    """Dense-SGD on the mesh == single-device full-batch SGD."""
    mesh = make_mesh((4, 2), ("data", "model"))
    opt = sgd_momentum(0.9)
    lr, steps = 0.05, 3
    params = init_params(CFG, jax.random.PRNGKey(0))
    dense_cfg = CompressionConfig(compressor="none")
    state = init_train_state(params, opt, workers=8, model_size=2,
                             compression=dense_cfg)
    step = make_train_step(CFG, mesh, opt, constant(lr), remat=False,
                           compression=dense_cfg)
    batch = _batch()
    for _ in range(steps):
        state, m = step(state, batch)

    p_sim = params
    mom = jax.tree.map(jnp.zeros_like, params)
    # mean over 4 data shards of per-shard mean loss == overall mean,
    # since shards are equal sized
    grad_fn = jax.jit(jax.grad(
        lambda p, b: loss_fn(p, CFG, b, remat=False)[0]))
    for _ in range(steps):
        gs = [grad_fn(p_sim, jax.tree.map(lambda x: x[w * 2:(w + 1) * 2],
                                          batch)) for w in range(4)]
        g = jax.tree.map(lambda *x: sum(x) / 4, *gs)
        mom = jax.tree.map(lambda m, g: 0.9 * m + g, mom, g)
        p_sim = jax.tree.map(lambda p, m: p - lr * m, p_sim, mom)
    err = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))),
        state["params"], p_sim)))
    assert err < 2e-5, f"max param deviation {err}"
    print("DENSE OK", err)


def check_adaptk():
    """Adaptive layer-wise density on the mesh == single-process
    simulation within 1e-7, for all three wire strategies (ISSUE 4
    acceptance criterion).

    allgather + gtopk (and the documented hierarchical->allgather
    fallback) run on the (4,2) mesh; the genuine two-level hierarchical
    path needs two data axes and runs on (2,2,2).  The simulation
    mirrors the mesh path's phases exactly: per-worker pass-A stats,
    worker-mean signal, one budget-exact allocation, dynamic-k
    selection, then the strategy's wire pattern.  Budget exactness on
    the mesh is asserted via the k_total metric.
    """
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core import adaptk
    from repro.dist import aggregate

    spec = get_compressor("topk")
    policy = adaptk.make_policy("variance")
    ratio, d, msize = 0.02, 407, 2
    d_pad, d_row = aggregate.flat_dims(d, msize)
    _, _, k_lo, k_hi, k_cap = aggregate.leaf_plan_adaptive(
        d, msize, ratio, spec, policy)

    def mesh_run(shape, axes_names, strategy, with_r2, g, e, r2):
        mesh = make_mesh(shape, axes_names)
        W = data_world_size(mesh)
        data_axes = tuple(a for a in axes_names if a != "model")
        joint = data_axes if len(data_axes) > 1 else data_axes[0]

        config = CompressionConfig(compressor="topk", ratio=ratio,
                                   strategy=strategy, backend="reference",
                                   density_policy=policy)

        def body(g_loc, e_loc, *r2_loc):
            r2t = {"w": r2_loc[0][0]} if r2_loc else None
            res = aggregate.aggregate_compressed(
                {"w": g_loc[0]}, {"w": e_loc[0]}, config, data_axes,
                "model", msize, jax.random.PRNGKey(7),
                resid2=r2t, world=W, step=jnp.int32(0))
            outs = (res.agg["w"], res.resid["w"][None],
                    res.metrics["k_total"])
            if r2_loc:
                outs += (res.resid2["w"][None],)
            return outs

        in_specs = (P(joint), P(joint)) + ((P(joint),) if with_r2 else ())
        out_specs = (P(), P(joint), P()) + ((P(joint),) if with_r2
                                            else ())
        sm = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs,
                           axis_names=set(data_axes), check_vma=False)
        args = (g, e) + ((r2,) if with_r2 else ())
        return jax.jit(sm)(*args)

    def simulate(W, n_pods, strategy, g, e, r2):
        u = [e[w] + jnp.pad(g[w], (0, d_pad - d)) for w in range(W)]
        sig = jnp.mean(jnp.stack([
            adaptk.leaf_signal("variance", d, jnp.sum(u[w]),
                               jnp.sum(u[w] * u[w]),
                               jnp.max(jnp.abs(u[w])))
            for w in range(W)]))
        K = adaptk.budget([d], ratio, policy, 0)
        k_alloc, K_eff = adaptk.allocate(K, sig[None], [k_lo], [k_hi])
        k_row = min(d_row, max(1, -(-int(k_alloc[0]) // msize)))

        def enc(flat):
            rows = flat.reshape(msize, d_row)
            v, i = jax.vmap(lambda r: adaptk.select_dynamic(
                spec, r, jnp.int32(k_row), k_cap))(rows)
            dec = jax.vmap(lambda vv, ii: codec.decode(vv, ii, d_row))(v, i)
            return v, i, dec

        partials, new_e = [], []
        for w in range(W):
            _, _, dec = enc(u[w])
            partials.append(dec)
            new_e.append(u[w] - dec.reshape(-1))
        if strategy == "gtopk":
            final, drops = aggregate.gtopk_simulate(partials, k_cap)
            mean = final / W
            new_e = [new_e[w] + drops[w].reshape(-1) for w in range(W)]
            new_r2 = None
        elif strategy == "hierarchical" and n_pods > 1:
            n_inner = W // n_pods
            pod_means = [sum(partials[p * n_inner + i]
                             for i in range(n_inner)) / n_inner
                         for p in range(n_pods)]
            dec2, new_r2 = [None] * W, [None] * W
            for w in range(W):
                u2 = r2[w] + pod_means[w // n_inner].reshape(-1)
                _, _, dd = enc(u2)
                dec2[w] = dd
                new_r2[w] = u2 - dd.reshape(-1)
            mean = sum(dec2[p * n_inner] for p in range(n_pods)) / n_pods
        else:   # allgather (and the hierarchical fallback on 1 data axis)
            mean = jnp.sum(jnp.stack(partials), axis=0) / W
            new_r2 = None
        return (mean.reshape(-1)[:d], jnp.stack(new_e), int(K_eff),
                jnp.stack(new_r2) if new_r2 else None)

    cases = [((4, 2), ("data", "model"), "allgather", 1, False),
             ((4, 2), ("data", "model"), "gtopk", 1, False),
             ((4, 2), ("data", "model"), "hierarchical", 1, True),
             ((2, 2, 2), ("pod", "data", "model"), "hierarchical", 2,
              True)]
    for shape, axes_names, strategy, n_pods, with_r2 in cases:
        W = 4
        g = jnp.stack([0.01 * jax.random.normal(jax.random.PRNGKey(w),
                                                (d,)) for w in range(W)])
        e = 0.001 * jax.random.normal(jax.random.PRNGKey(99), (W, d_pad))
        r2 = (0.0005 * jax.random.normal(jax.random.PRNGKey(123),
                                         (W, d_pad)) if with_r2 else None)
        outs = mesh_run(shape, axes_names, strategy, with_r2, g, e, r2)
        agg_m, e_m, k_tot = outs[0], outs[1], outs[2]
        agg_s, e_s, K_eff, r2_s = simulate(W, n_pods, strategy, g, e, r2)
        agg_err = float(jnp.max(jnp.abs(agg_m - agg_s)))
        e_err = float(jnp.max(jnp.abs(e_m - e_s)))
        assert int(k_tot) == K_eff, (strategy, int(k_tot), K_eff)
        assert agg_err < 1e-7, (strategy, shape, agg_err)
        assert e_err < 1e-7, (strategy, shape, e_err)
        if with_r2 and n_pods > 1:
            r2_err = float(jnp.max(jnp.abs(outs[3] - r2_s)))
            assert r2_err < 1e-7, (strategy, shape, r2_err)
        print(f"  adaptk {strategy} on {shape}: agg_err={agg_err:.2e} "
              f"e_err={e_err:.2e} k_total={int(k_tot)}")
    print("ADAPTK OK")


def check_rtopk():
    """Fixed-k rTop-k on the mesh == single-process simulation within
    1e-7, for all three wire strategies (ISSUE 7 acceptance criterion),
    plus the adaptive global-k (normdecay) controller path: the budget
    the mesh reports must equal the simulated norm-decay-scaled budget
    and the controller scalars must round-trip through the step.

    The simulation mirrors the mesh path's key derivation exactly
    (``lkey = fold_in(key, leaf_key_salt("w"))``, then one
    ``jax.random.split(lkey, model_size)`` per compression), so the
    strided r-samples — and with them every selected index — agree.
    """
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core import adaptk
    from repro.dist import aggregate

    spec = get_compressor("rtopk")
    ratio, d, msize = 0.02, 407, 2
    d_pad, d_row, k_row, k_cap = aggregate.leaf_plan(d, msize, ratio, spec)
    lkey = jax.random.fold_in(jax.random.PRNGKey(7),
                              aggregate.leaf_key_salt("w"))

    def mesh_run(shape, axes_names, strategy, with_r2, g, e, r2):
        mesh = make_mesh(shape, axes_names)
        W = data_world_size(mesh)
        data_axes = tuple(a for a in axes_names if a != "model")
        joint = data_axes if len(data_axes) > 1 else data_axes[0]

        config = CompressionConfig(compressor="rtopk", ratio=ratio,
                                   strategy=strategy, backend="reference")

        def body(g_loc, e_loc, *r2_loc):
            r2t = {"w": r2_loc[0][0]} if r2_loc else None
            res = aggregate.aggregate_compressed(
                {"w": g_loc[0]}, {"w": e_loc[0]}, config, data_axes,
                "model", msize, jax.random.PRNGKey(7),
                resid2=r2t, world=W)
            outs = (res.agg["w"], res.resid["w"][None])
            if r2_loc:
                outs += (res.resid2["w"][None],)
            return outs

        in_specs = (P(joint), P(joint)) + ((P(joint),) if with_r2 else ())
        out_specs = (P(), P(joint)) + ((P(joint),) if with_r2 else ())
        sm = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs,
                           axis_names=set(data_axes), check_vma=False)
        args = (g, e) + ((r2,) if with_r2 else ())
        return jax.jit(sm)(*args)

    def enc(flat, key):
        rows = flat.reshape(msize, d_row)
        keys = jax.random.split(key, msize)
        v, i = jax.vmap(lambda r, kk: spec.select(r, k_row, kk))(rows,
                                                                 keys)
        dec = jax.vmap(lambda vv, ii: codec.decode(vv, ii, d_row))(v, i)
        return v, i, dec

    def simulate(W, n_pods, strategy, g, e, r2):
        u = [e[w] + jnp.pad(g[w], (0, d_pad - d)) for w in range(W)]
        partials, new_e = [], []
        for w in range(W):
            _, _, dec = enc(u[w], lkey)
            partials.append(dec)
            new_e.append(u[w] - dec.reshape(-1))
        if strategy == "gtopk":
            final, drops = aggregate.gtopk_simulate(partials, k_cap)
            mean = final / W
            new_e = [new_e[w] + drops[w].reshape(-1) for w in range(W)]
            new_r2 = None
        elif strategy == "hierarchical" and n_pods > 1:
            n_inner = W // n_pods
            pod_means = [sum(partials[p * n_inner + i]
                             for i in range(n_inner)) / n_inner
                         for p in range(n_pods)]
            dec2, new_r2 = [None] * W, [None] * W
            for w in range(W):
                u2 = r2[w] + pod_means[w // n_inner].reshape(-1)
                _, _, dd = enc(u2, jax.random.fold_in(lkey, 1))
                dec2[w] = dd
                new_r2[w] = u2 - dd.reshape(-1)
            mean = sum(dec2[p * n_inner] for p in range(n_pods)) / n_pods
        else:   # allgather (and the hierarchical fallback on 1 data axis)
            mean = jnp.sum(jnp.stack(partials), axis=0) / W
            new_r2 = None
        return (mean.reshape(-1)[:d], jnp.stack(new_e),
                jnp.stack(new_r2) if new_r2 else None)

    cases = [((4, 2), ("data", "model"), "allgather", 1, False),
             ((4, 2), ("data", "model"), "gtopk", 1, False),
             ((4, 2), ("data", "model"), "hierarchical", 1, True),
             ((2, 2, 2), ("pod", "data", "model"), "hierarchical", 2,
              True)]
    for shape, axes_names, strategy, n_pods, with_r2 in cases:
        W = 4
        g = jnp.stack([0.01 * jax.random.normal(jax.random.PRNGKey(w),
                                                (d,)) for w in range(W)])
        e = 0.001 * jax.random.normal(jax.random.PRNGKey(99), (W, d_pad))
        r2 = (0.0005 * jax.random.normal(jax.random.PRNGKey(123),
                                         (W, d_pad)) if with_r2 else None)
        outs = mesh_run(shape, axes_names, strategy, with_r2, g, e, r2)
        agg_s, e_s, r2_s = simulate(W, n_pods, strategy, g, e, r2)
        agg_err = float(jnp.max(jnp.abs(outs[0] - agg_s)))
        e_err = float(jnp.max(jnp.abs(outs[1] - e_s)))
        assert agg_err < 1e-7, (strategy, shape, agg_err)
        assert e_err < 1e-7, (strategy, shape, e_err)
        if with_r2 and n_pods > 1:
            r2_err = float(jnp.max(jnp.abs(outs[2] - r2_s)))
            assert r2_err < 1e-7, (strategy, shape, r2_err)
        print(f"  rtopk {strategy} on {shape}: agg_err={agg_err:.2e} "
              f"e_err={e_err:.2e}")

    # -- adaptive rTop-k + global-k controller on the (4,2) mesh --
    policy = adaptk.make_policy("variance", global_policy="normdecay",
                                global_ema=0.0, global_floor=0.25)
    _, _, k_lo, k_hi, k_cap_a = aggregate.leaf_plan_adaptive(
        d, msize, ratio, spec, policy)
    mesh = make_mesh((4, 2), ("data", "model"))
    W = 4

    gk_config = CompressionConfig(compressor="rtopk", ratio=ratio,
                                  backend="reference",
                                  density_policy=policy)

    def body(g_loc, e_loc, st_loc):
        res = aggregate.aggregate_compressed(
            {"w": g_loc[0]}, {"w": e_loc[0]}, gk_config, ("data",),
            "model", msize, jax.random.PRNGKey(7), world=W,
            adapt_state=st_loc, step=jnp.int32(0))
        return (res.agg["w"], res.resid["w"][None], res.adapt_state,
                res.metrics["k_total"])

    run = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("data"), P("data"), P()),
        out_specs=(P(), P("data"), P(), P()),
        axis_names={"data"}, check_vma=False))

    def sim_step(g, e, state):
        u = [e[w] + jnp.pad(g[w], (0, d_pad - d)) for w in range(W)]
        sig = jnp.mean(jnp.stack([
            adaptk.leaf_signal("variance", d, jnp.sum(u[w]),
                               jnp.sum(u[w] * u[w]),
                               jnp.max(jnp.abs(u[w])))
            for w in range(W)]))
        sq_tot = jnp.mean(jnp.stack([jnp.sum(u[w] * u[w])
                                     for w in range(W)]))
        signal, state = adaptk.blend_signal(state, sig[None], policy.ema)
        scale, upd = adaptk.global_scale(state, sq_tot, policy)
        state = {**state, **upd}
        K = adaptk.scale_budget(adaptk.budget([d], ratio, policy, 0),
                                scale)
        _, K_eff = adaptk.allocate(K, signal, [k_lo], [k_hi])
        return int(K_eff), state

    g = jnp.stack([0.01 * jax.random.normal(jax.random.PRNGKey(w), (d,))
                   for w in range(W)])
    e = 0.001 * jax.random.normal(jax.random.PRNGKey(99), (W, d_pad))
    state = adaptk.init_controller_state(1, global_k=True)
    sstate = {k: v for k, v in state.items()}
    for i, sc in enumerate((1.0, 0.5, 0.25)):
        _, ne_m, state, kt = run(sc * g, sc * e, state)
        K_sim, sstate = sim_step(sc * g, sc * e, sstate)
        assert int(kt) == K_sim, (i, int(kt), K_sim)
        for kk in ("gnorm", "gnorm0"):
            err = abs(float(state[kk]) - float(sstate[kk]))
            assert err < 1e-5 * max(1.0, float(sstate[kk])), (i, kk, err)
        e = ne_m / sc  # keep residual state evolving step to step
        print(f"  rtopk globalk step {i}: k_total={int(kt)} "
              f"gnorm={float(state['gnorm']):.4g}")
    assert float(state["gnorm0"]) > 0.0
    print("RTOPK OK")


def check_bucketed():
    """Bucketed aggregation (ISSUE 5) == per-leaf aggregation BIT-exactly
    on real meshes, for all three wire strategies, fixed-k and adaptive,
    reference and fused backends — plus the jaxpr collective-count
    assertion on the same traced programs: one codec-pair collective per
    wire level per step (log2(W) ppermute rounds for gTop-k),
    independent of leaf count."""
    from jax.sharding import PartitionSpec as P

    from repro.core.adaptk import make_policy
    from repro.dist import aggregate
    from repro.dist.layout import build_layout, pack_residual_arrays
    from repro.launch.hlo_cost import count_wire_collectives

    params = {"a": jnp.zeros((33, 5)), "n": {"b": jnp.zeros((7,)),
                                             "c": jnp.zeros((19, 3))}}
    L = len(jax.tree.leaves(params))
    ratio = 0.05

    def run_case(shape, axes_names, strategy, *, policy=None,
                 with_r2=False, backend="reference", comp="topk",
                 momentum=0.0, expect=None):
        mesh = make_mesh(shape, axes_names)
        msize = model_axis_size(mesh)
        W = data_world_size(mesh)
        data_axes = tuple(a for a in axes_names if a != "model")
        joint = data_axes if len(data_axes) > 1 else data_axes[0]
        spec = get_compressor(comp)
        layout = build_layout(params, msize, ratio, spec,
                              density_policy=policy)

        key = jax.random.PRNGKey(1)
        g_stack = jax.tree.map(
            lambda p: 0.01 * jax.random.normal(
                jax.random.fold_in(key, p.size), (W,) + p.shape), params)
        e_tree = jax.tree.map(
            lambda p: 1e-3 * jax.random.normal(
                jax.random.fold_in(key, p.size + 1),
                (W, -(-p.size // msize) * msize)), params)
        e_flat = jnp.asarray(pack_residual_arrays(
            layout, [np.asarray(x) for x in jax.tree.leaves(e_tree)]))
        r2_tree = (jax.tree.map(lambda e: 0.5 * e, e_tree)
                   if with_r2 else None)
        r2_flat = (jnp.asarray(pack_residual_arrays(
            layout, [np.asarray(x) for x in jax.tree.leaves(r2_tree)]))
            if with_r2 else None)
        config = CompressionConfig(compressor=comp, ratio=ratio,
                                   strategy=strategy, backend=backend,
                                   momentum_correction=momentum,
                                   density_policy=policy)
        kw = dict(world=W, step=jnp.int32(0) if policy else None)

        def per_leaf(g, e, *r2s):
            r2 = jax.tree.map(lambda x: x[0], r2s[0]) if r2s else None
            res = aggregate.aggregate_compressed(
                jax.tree.map(lambda x: x[0], g),
                jax.tree.map(lambda x: x[0], e), config, data_axes,
                "model", msize, jax.random.PRNGKey(7), resid2=r2, **kw)
            out = (res.agg, jax.tree.map(lambda x: x[None], res.resid),
                   res.metrics)
            return out + ((jax.tree.map(lambda x: x[None], res.resid2),)
                          if r2s else ())

        def bucketed(g, e, *r2s):
            res = aggregate.aggregate_bucketed(
                jax.tree.map(lambda x: x[0], g), e[0], layout, config,
                data_axes, "model", jax.random.PRNGKey(7),
                resid2=r2s[0][0] if r2s else None, **kw)
            out = (res.agg, res.resid[None], res.metrics)
            return out + ((res.resid2[None],) if r2s else ())

        sm1 = jax.shard_map(
            per_leaf, mesh=mesh, in_specs=(P(joint),) * (2 + with_r2),
            out_specs=(P(), P(joint), P()) + ((P(joint),) if with_r2
                                              else ()),
            axis_names=set(data_axes), check_vma=False)
        sm2 = jax.shard_map(
            bucketed, mesh=mesh, in_specs=(P(joint),) * (2 + with_r2),
            out_specs=(P(), P(joint), P()) + ((P(joint),) if with_r2
                                              else ()),
            axis_names=set(data_axes), check_vma=False)
        args1 = (g_stack, e_tree) + ((r2_tree,) if with_r2 else ())
        args2 = (g_stack, e_flat) + ((r2_flat,) if with_r2 else ())
        out1 = jax.jit(sm1)(*args1)
        out2 = jax.jit(sm2)(*args2)

        # bit-exact agreement: aggregate, residuals (both levels), metrics
        for pa, pb in zip(jax.tree.leaves(out1[0]),
                          jax.tree.leaves(out2[0])):
            assert np.array_equal(np.asarray(pa), np.asarray(pb)), \
                (shape, strategy, "agg")
        e1 = pack_residual_arrays(layout, [
            np.asarray(x) for x in jax.tree.leaves(out1[1])])
        assert np.array_equal(e1, np.asarray(out2[1])), \
            (shape, strategy, "resid")
        if with_r2:
            r21 = pack_residual_arrays(layout, [
                np.asarray(x) for x in jax.tree.leaves(out1[3])])
            assert np.array_equal(r21, np.asarray(out2[3])), \
                (shape, strategy, "resid2")
        for mk in ("density", "density_cap", "comm_bits_sparse",
                   "comm_bits_dense", "wire_bytes"):
            assert float(out1[2][mk]) == float(out2[2][mk]), \
                (shape, strategy, mk)
        if policy is not None:
            assert float(out1[2]["k_total"]) == float(out2[2]["k_total"])

        # collective counts from the traced jaxprs: bucketed is
        # leaf-count independent, per-leaf scales with L
        c1 = count_wire_collectives(jax.make_jaxpr(sm1)(*args1))
        c2 = count_wire_collectives(jax.make_jaxpr(sm2)(*args2))
        if expect is not None:
            want_ag, want_pp = expect
            assert (c2["all_gather"], c2["ppermute"]) == \
                (want_ag, want_pp), (shape, strategy, c2)
            assert (c1["all_gather"], c1["ppermute"]) == \
                (want_ag * L, want_pp * L), (shape, strategy, c1)
        print(f"  bucketed {strategy} on {shape} "
              f"policy={policy.policy if policy else 'fixed'} "
              f"backend={backend} mc={momentum}: bit-equal, "
              f"collectives {c1} -> {c2}")

    pol = make_policy("variance")
    # (4,2): one data axis of 4 workers
    run_case((4, 2), ("data", "model"), "allgather", expect=(2, 0))
    run_case((4, 2), ("data", "model"), "gtopk", expect=(0, 4))
    # hierarchical on one data axis: documented fallback to allgather
    run_case((4, 2), ("data", "model"), "hierarchical", with_r2=True,
             expect=(2, 0))
    run_case((4, 2), ("data", "model"), "allgather", policy=pol,
             expect=(2, 0))
    run_case((4, 2), ("data", "model"), "gtopk", policy=pol,
             expect=(0, 4))
    run_case((4, 2), ("data", "model"), "allgather", comp="gaussiank",
             backend="auto", expect=(2, 0))      # fused segmented kernels
    run_case((4, 2), ("data", "model"), "gtopk", comp="gaussiank",
             backend="auto", expect=(0, 4))      # fused x gtopk
    run_case((4, 2), ("data", "model"), "allgather", policy=pol,
             comp="gaussiank", backend="auto",
             expect=(2, 0))   # adaptive x fused: segmented pass-A reuse
    run_case((4, 2), ("data", "model"), "allgather", momentum=0.9,
             with_r2=True, expect=(2, 0))        # DGC momentum correction
    # (2,2,2): two data axes — genuine two-level hierarchical + gtopk
    # rounds crossing BOTH axes
    run_case((2, 2, 2), ("pod", "data", "model"), "hierarchical",
             with_r2=True, expect=(4, 0))
    run_case((2, 2, 2), ("pod", "data", "model"), "hierarchical",
             comp="gaussiank", backend="auto", with_r2=True,
             expect=(4, 0))   # fused x two-level hierarchical
    run_case((2, 2, 2), ("pod", "data", "model"), "hierarchical",
             with_r2=True, policy=pol, expect=(4, 0))
    run_case((2, 2, 2), ("pod", "data", "model"), "gtopk", expect=(0, 4))
    print("BUCKETED OK")


def check_chunked():
    """Chunked bucket schedule (ISSUE 6) == unchunked bucketed BIT-exactly
    on real meshes: the chunk plan only re-dispatches the wire over
    leaf-aligned windows of the same flat buffer, so aggregate, both
    residual levels and every metric except ``collectives_per_step``
    must be bitwise identical at any chunk count — while the traced
    jaxpr must show exactly N x the per-level collectives (the whole
    point: N independently schedulable wire messages)."""
    from jax.sharding import PartitionSpec as P

    from repro.core.adaptk import make_policy
    from repro.dist import aggregate
    from repro.dist.layout import build_chunk_plan, build_layout
    from repro.launch.hlo_cost import count_wire_collectives

    params = {"a": jnp.zeros((33, 5)), "n": {"b": jnp.zeros((7,)),
                                             "c": jnp.zeros((19, 3)),
                                             "d": jnp.zeros((41,))},
              "z": jnp.zeros((13, 2))}
    L = len(jax.tree.leaves(params))
    ratio = 0.05

    def run_case(shape, axes_names, strategy, n_chunks, *, policy=None,
                 with_r2=False, backend="reference", comp="topk",
                 expect=None):
        mesh = make_mesh(shape, axes_names)
        msize = model_axis_size(mesh)
        W = data_world_size(mesh)
        data_axes = tuple(a for a in axes_names if a != "model")
        joint = data_axes if len(data_axes) > 1 else data_axes[0]
        spec = get_compressor(comp)
        layout = build_layout(params, msize, ratio, spec,
                              density_policy=policy)
        plan = build_chunk_plan(layout, n_chunks)
        N = plan.n_chunks          # may be clamped below n_chunks

        key = jax.random.PRNGKey(1)
        g_stack = jax.tree.map(
            lambda p: 0.01 * jax.random.normal(
                jax.random.fold_in(key, p.size), (W,) + p.shape), params)
        e_flat = 1e-3 * jax.random.normal(
            jax.random.fold_in(key, 2), (W, layout.flat_size))
        r2_flat = 0.5 * e_flat if with_r2 else None
        config = CompressionConfig(compressor=comp, ratio=ratio,
                                   strategy=strategy, backend=backend,
                                   density_policy=policy)
        kw = dict(world=W, step=jnp.int32(0) if policy else None)

        def unchunked(g, e, *r2s):
            res = aggregate.aggregate_bucketed(
                jax.tree.map(lambda x: x[0], g), e[0], layout, config,
                data_axes, "model", jax.random.PRNGKey(7),
                resid2=r2s[0][0] if r2s else None, **kw)
            out = (res.agg, res.resid[None], res.metrics)
            return out + ((res.resid2[None],) if r2s else ())

        def chunked(g, e, *r2s):
            res = aggregate.aggregate_bucketed_chunked(
                jax.tree.map(lambda x: x[0], g), e[0], layout, plan, config,
                data_axes, "model", jax.random.PRNGKey(7),
                resid2=r2s[0][0] if r2s else None, **kw)
            out = (res.agg, res.resid[None], res.metrics)
            return out + ((res.resid2[None],) if r2s else ())

        specs = dict(
            in_specs=(P(joint),) * (2 + with_r2),
            out_specs=(P(), P(joint), P()) + ((P(joint),) if with_r2
                                              else ()))
        sm1 = jax.shard_map(unchunked, mesh=mesh,
                            axis_names=set(data_axes),
                            check_vma=False, **specs)
        sm2 = jax.shard_map(chunked, mesh=mesh,
                            axis_names=set(data_axes),
                            check_vma=False, **specs)
        args = (g_stack, e_flat) + ((r2_flat,) if with_r2 else ())
        out1 = jax.jit(sm1)(*args)
        out2 = jax.jit(sm2)(*args)

        for pa, pb in zip(jax.tree.leaves(out1[0]),
                          jax.tree.leaves(out2[0])):
            assert np.array_equal(np.asarray(pa), np.asarray(pb)), \
                (shape, strategy, N, "agg")
        assert np.array_equal(np.asarray(out1[1]), np.asarray(out2[1])), \
            (shape, strategy, N, "resid")
        if with_r2:
            assert np.array_equal(np.asarray(out1[3]),
                                  np.asarray(out2[3])), \
                (shape, strategy, N, "resid2")
        for mk in ("density", "density_cap", "comm_bits_sparse",
                   "comm_bits_dense", "wire_bytes"):
            assert float(out1[2][mk]) == float(out2[2][mk]), \
                (shape, strategy, N, mk)
        if policy is not None:
            assert float(out1[2]["k_total"]) == float(out2[2]["k_total"])
        # the ONE sanctioned metric difference: N x the wire messages
        assert float(out2[2]["collectives_per_step"]) == \
            N * float(out1[2]["collectives_per_step"]), \
            (shape, strategy, N, out1[2]["collectives_per_step"],
             out2[2]["collectives_per_step"])

        # jaxpr structure: chunked == N x unchunked per wire primitive
        c1 = count_wire_collectives(jax.make_jaxpr(sm1)(*args))
        c2 = count_wire_collectives(jax.make_jaxpr(sm2)(*args))
        for prim in ("all_gather", "ppermute"):
            assert c2[prim] == N * c1[prim], (shape, strategy, N, prim,
                                              c1, c2)
        if expect is not None:
            want_ag, want_pp = expect
            assert (c2["all_gather"], c2["ppermute"]) == \
                (want_ag * N, want_pp * N), (shape, strategy, N, c2)
        print(f"  chunked N={N}(req {n_chunks}) {strategy} on {shape} "
              f"policy={policy.policy if policy else 'fixed'} "
              f"backend={backend}: bit-equal, collectives {c1} -> {c2}")

    pol = make_policy("variance")
    # (4,2): all strategies x {fixed, adaptive} x {reference, fused}
    run_case((4, 2), ("data", "model"), "allgather", 2, expect=(2, 0))
    run_case((4, 2), ("data", "model"), "allgather", 3, policy=pol,
             expect=(2, 0))
    run_case((4, 2), ("data", "model"), "gtopk", 2, expect=(0, 4))
    run_case((4, 2), ("data", "model"), "gtopk", 2, policy=pol,
             expect=(0, 4))
    run_case((4, 2), ("data", "model"), "hierarchical", 2, with_r2=True,
             expect=(2, 0))    # documented fallback to allgather
    run_case((4, 2), ("data", "model"), "allgather", 2, comp="gaussiank",
             backend="auto", expect=(2, 0))   # fused segmented kernels
    run_case((4, 2), ("data", "model"), "allgather", 2, comp="gaussiank",
             backend="auto", policy=pol,
             expect=(2, 0))    # adaptive x fused: global pass-A barrier
    # requesting more chunks than leaves clamps to L (= 5 segments)
    run_case((4, 2), ("data", "model"), "allgather", 8, expect=(2, 0))
    # (2,2,2): genuine two-level hierarchical + cross-axis gtopk
    run_case((2, 2, 2), ("pod", "data", "model"), "hierarchical", 2,
             with_r2=True, expect=(4, 0))
    run_case((2, 2, 2), ("pod", "data", "model"), "hierarchical", 2,
             with_r2=True, policy=pol, expect=(4, 0))
    run_case((2, 2, 2), ("pod", "data", "model"), "hierarchical", 2,
             with_r2=True, comp="gaussiank", backend="auto",
             expect=(4, 0))
    run_case((2, 2, 2), ("pod", "data", "model"), "gtopk", 2,
             expect=(0, 4))
    print("CHUNKED OK")


def check_serve():
    """Train-to-serve delta streaming (DESIGN.md §13) against a REAL
    training run on the (4,2) mesh: the trainer publishes after every
    step (resync every 2nd publish), a serving replica ingests each
    message, and the publisher invariants are checked at every tick:

    * replica params BIT-equal to trainer params at every full-resync
      epoch (the acceptance invariant);
    * the published view ``pub`` bit-equal to the packed replica params
      at EVERY publish (pub literally is the replica's state);
    * the true staleness gap ``pack(trainer) - pack(replica)`` equal to
      the publish residual to float tolerance at delta epochs;
    * delta wire bits exactly ``layout.pair_bits``; resync bits exactly
      the dense bucket;
    * the sharded jitted subscriber (``make_apply_delta`` with
      ``serve_param_specs``) bit-equal to the host ``apply_delta``.
    """
    from repro.dist.layout import pack_grads, rebudget_layout
    from repro.serve import (RESYNC, apply_delta, apply_message,
                             init_publisher_state, make_apply_delta,
                             message_bits, publish)

    mesh = make_mesh((4, 2), ("data", "model"))
    W = data_world_size(mesh)
    msize = model_axis_size(mesh)
    opt = sgd_momentum(0.9)
    train_cfg = CompressionConfig(compressor="gaussiank", ratio=0.02)
    params = init_params(CFG, jax.random.PRNGKey(0))
    state = init_train_state(params, opt, workers=W, model_size=msize,
                             compression=train_cfg)
    step = make_train_step(CFG, mesh, opt, constant(0.05),
                           compression=train_cfg, remat=False)

    from repro.dist.layout import build_layout
    pub_config = CompressionConfig(compressor="topk", ratio=0.05,
                                   backend="reference")
    # delta-layout reuse: re-budget the gradient-wire layout at the
    # publish ratio — row geometry identical, codec capacities fixed-k
    train_layout = build_layout(params, msize, train_cfg)
    layout = rebudget_layout(train_layout, pub_config.ratio,
                             pub_config.spec)
    assert layout.d_row_total == train_layout.d_row_total
    assert [s.row_off for s in layout.segments] == \
        [s.row_off for s in train_layout.segments]

    pub_state = init_publisher_state(layout)
    # two replica chains: the host chain (apply_message on host arrays)
    # carries the invariant checks; the device chain (jitted sharded
    # subscriber) must track it bitwise leaf-for-leaf
    replica = jax.tree.map(lambda x: np.zeros_like(np.asarray(x)), params)
    replica_dev = replica
    apply_jit = make_apply_delta(layout, mesh, replica)
    key = jax.random.PRNGKey(7)
    batch = _batch()
    n_resync = n_delta = 0
    for t in range(5):
        state, _ = step(state, batch)
        trainer_params = jax.device_get(state["params"])
        pub_state, msg = publish(pub_state, trainer_params, layout,
                                 pub_config, key, resync_every=2)
        if msg.kind != RESYNC:
            replica = apply_delta(replica, layout, msg.values,
                                  msg.indices)
            replica_dev = apply_jit(replica_dev, msg.values, msg.indices)
            # sharded jitted subscriber == host subscriber, bitwise
            for a, b in zip(jax.tree.leaves(jax.device_get(replica_dev)),
                            jax.tree.leaves(replica)):
                assert np.array_equal(a, np.asarray(b)), t
            assert message_bits(msg) == layout.pair_bits(None), t
            n_delta += 1
        else:
            replica = apply_message(replica, layout, msg)
            replica_dev = replica
            assert message_bits(msg) == \
                layout.model_size * layout.d_row_total * 32, t
            n_resync += 1
            # acceptance invariant: replica == trainer EXACTLY
            for a, b in zip(jax.tree.leaves(replica),
                            jax.tree.leaves(trainer_params)):
                assert np.array_equal(np.asarray(a), np.asarray(b)), t
        P = pack_grads(layout, trainer_params, jnp.float32)
        R = pack_grads(layout, jax.device_get(replica), jnp.float32)
        # pub IS the replica's packed state, bitwise, at every publish
        assert np.array_equal(np.asarray(pub_state["pub"]),
                              np.asarray(R)), t
        # staleness gap == the publish residual (how staleness is
        # observed for free: |resid| is on-device already)
        np.testing.assert_allclose(np.asarray(P - R),
                                   np.asarray(pub_state["resid"]),
                                   rtol=0, atol=1e-5)
    assert n_resync >= 2 and n_delta >= 2, (n_resync, n_delta)
    print("SERVE OK")


def check_multipod():
    """Every compressor trains (loss decreases) on the 2x2x2 pod mesh;
    gaussiank additionally through every wire strategy (the gtopk rounds
    there cross BOTH data axes: one ppermute over "data", one over
    "pod")."""
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    opt = sgd_momentum(0.9)
    params = init_params(CFG, jax.random.PRNGKey(0))
    batch = _batch()
    for comp in ("topk", "randk", "gaussiank", "dgck", "trimmedk"):
        strategies = (("allgather", "hierarchical", "gtopk")
                      if comp == "gaussiank" else ("allgather",))
        for strat in strategies:
            config = CompressionConfig(compressor=comp, ratio=0.02,
                                       strategy=strat)
            state = init_train_state(params, opt, workers=4, model_size=2,
                                     compression=config)
            step = make_train_step(CFG, mesh, opt, constant(0.05),
                                   compression=config, remat=False)
            losses = []
            for _ in range(6):
                state, m = step(state, batch)
                losses.append(float(m["loss"]))
            assert losses[-1] < losses[0], (comp, strat, losses)
            assert np.isfinite(losses).all()
    print("MULTIPOD OK")


def check_hier_gtopk():
    """The hier_gtopk hybrid (pod gather + cross-pod gTop-k, ISSUE 9)
    on the mesh == single-process simulation within 1e-6, at n_pods=2
    (where it must also equal plain hierarchical bit-for-bit — same
    algorithm: one XOR round == a 2-party gather) and n_pods=4 (genuine
    multi-round recursive doubling across pods).

    The simulation mirrors the mesh phases exactly: per-worker EF
    compress, pod gather+mean, second-level compress of the pod mean
    against the pod-replicated resid2, then ``gtopk_simulate`` over one
    representative per pod with the merge drop credited to resid2
    UN-divided (resid2 is pod-replicated, so summing one representative
    per pod recovers the dropped mass exactly once).  Also asserts:

    * resid2 stays pod-replicated (max deviation inside a pod == 0);
    * the two-level conservation invariant
      ``sum_w u_w + n_inner*sum_rep r2 ==
        W*agg + sum_w e' + n_inner*sum_rep r2'``;
    * ``collectives_per_step == 1 + log2(n_pods)`` (one inner gather
      plus the outer ppermute rounds — the wire shape the tuner prices).
    """
    import math

    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.dist import aggregate

    spec = get_compressor("topk")
    ratio, d = 0.02, 407

    def mesh_run(shape, axes_names, strategy, g, e, r2):
        mesh = make_mesh(shape, axes_names)
        W = data_world_size(mesh)
        msize = model_axis_size(mesh)
        data_axes = tuple(a for a in axes_names if a != "model")
        joint = data_axes if len(data_axes) > 1 else data_axes[0]
        config = CompressionConfig(compressor="topk", ratio=ratio,
                                   strategy=strategy, backend="reference")

        def body(g_loc, e_loc, r2_loc):
            res = aggregate.aggregate_compressed(
                {"w": g_loc[0]}, {"w": e_loc[0]}, config, data_axes,
                "model", msize, jax.random.PRNGKey(7),
                resid2={"w": r2_loc[0]}, world=W)
            return (res.agg["w"], res.resid["w"][None],
                    res.resid2["w"][None],
                    res.metrics["collectives_per_step"])

        sm = jax.shard_map(body, mesh=mesh,
                           in_specs=(P(joint), P(joint), P(joint)),
                           out_specs=(P(), P(joint), P(joint), P()),
                           axis_names=set(data_axes), check_vma=False)
        return jax.jit(sm)(g, e, r2)

    def simulate(W, n_pods, msize, g, e, r2):
        n_inner = W // n_pods
        d_pad, d_row = aggregate.flat_dims(d, msize)
        _, _, k_row, k_cap = aggregate.leaf_plan(d, msize, ratio, spec)
        outs = [aggregate.compress_worker(g[w], e[w], spec, ratio, msize,
                                          None) for w in range(W)]
        partials = [jax.vmap(lambda v, i: codec.decode(v, i, d_row))(
            o[0], o[1]) for o in outs]
        pod_means = [sum(partials[p * n_inner + i]
                         for i in range(n_inner)) / n_inner
                     for p in range(n_pods)]
        dec2, local2 = [None] * W, [None] * W
        for w in range(W):
            u2 = r2[w] + pod_means[w // n_inner].reshape(-1)
            rows = u2.reshape(msize, d_row)
            v2, i2 = jax.vmap(lambda r: spec.select(r, k_row, None))(rows)
            dec2[w] = jax.vmap(
                lambda vv, ii: codec.decode(vv, ii, d_row))(v2, i2)
            local2[w] = u2 - dec2[w].reshape(-1)
        final, drops = aggregate.gtopk_simulate(
            [dec2[p * n_inner] for p in range(n_pods)], k_cap)
        mean = final / n_pods
        new_e = jnp.stack([outs[w][2] for w in range(W)])
        new_r2 = jnp.stack(
            [local2[w] + drops[w // n_inner].reshape(-1)
             for w in range(W)])
        return mean.reshape(-1)[:d], new_e, new_r2

    for shape, axes_names, n_pods in [
            ((2, 2, 2), ("pod", "data", "model"), 2),
            ((4, 2, 1), ("pod", "data", "model"), 4)]:
        W = shape[0] * shape[1]
        msize = shape[2]
        n_inner = W // n_pods
        d_pad, _ = aggregate.flat_dims(d, msize)
        g = jnp.stack([0.01 * jax.random.normal(jax.random.PRNGKey(w),
                                                (d,)) for w in range(W)])
        # keep the padding tail zero so the truncated agg reconstructs
        # the dense mean exactly in the conservation check below
        e = 0.001 * jax.random.normal(
            jax.random.PRNGKey(99), (W, d_pad)).at[:, d:].set(0.0)
        # resid2 is pod-replicated by construction (zero init, identical
        # second-level inputs per pod) — feed it that way
        r2 = jnp.repeat(0.0005 * jax.random.normal(
            jax.random.PRNGKey(123),
            (n_pods, d_pad)).at[:, d:].set(0.0), n_inner, axis=0)
        agg_m, e_m, r2_m, colls = mesh_run(shape, axes_names,
                                           "hier_gtopk", g, e, r2)
        agg_s, e_s, r2_s = simulate(W, n_pods, msize, g, e, r2)
        agg_err = float(jnp.max(jnp.abs(agg_m - agg_s)))
        e_err = float(jnp.max(jnp.abs(e_m - e_s)))
        r2_err = float(jnp.max(jnp.abs(r2_m - r2_s)))
        assert agg_err < 1e-6, (shape, agg_err)
        assert e_err < 1e-6, (shape, e_err)
        assert r2_err < 1e-6, (shape, r2_err)
        assert int(colls) == 1 + int(math.log2(n_pods)), (shape, colls)
        # resid2 stays pod-replicated
        r2_pods = r2_m.reshape(n_pods, n_inner, d_pad)
        rep_dev = float(jnp.max(jnp.abs(r2_pods - r2_pods[:, :1])))
        assert rep_dev == 0.0, (shape, rep_dev)
        # two-level conservation (one resid2 representative per pod,
        # input representatives on the left, output on the right)
        u_sum = jnp.sum(e + jnp.pad(g, ((0, 0), (0, d_pad - d))), axis=0)
        lhs = u_sum + n_inner * jnp.sum(
            r2.reshape(n_pods, n_inner, d_pad)[:, 0], axis=0)
        rhs = (jnp.pad(agg_m * W, (0, d_pad - d)) + jnp.sum(e_m, axis=0)
               + n_inner * jnp.sum(r2_pods[:, 0], axis=0))
        cons = float(jnp.max(jnp.abs(lhs - rhs)))
        assert cons < 1e-6, (shape, cons)
        print(f"  hier_gtopk on {shape} (P={n_pods}): agg_err={agg_err:.2e}"
              f" r2_err={r2_err:.2e} cons={cons:.2e} colls={int(colls)}")

    # n_pods=2 degenerate case: the hybrid IS plain hierarchical (one
    # XOR round == 2-party gather) — outputs must match bit-for-bit
    shape, axes_names = (2, 2, 2), ("pod", "data", "model")
    W, msize, n_pods, n_inner = 4, 2, 2, 2
    d_pad, _ = aggregate.flat_dims(d, msize)
    g = jnp.stack([0.01 * jax.random.normal(jax.random.PRNGKey(w), (d,))
                   for w in range(W)])
    e = 0.001 * jax.random.normal(jax.random.PRNGKey(99), (W, d_pad))
    r2 = jnp.repeat(0.0005 * jax.random.normal(
        jax.random.PRNGKey(123), (n_pods, d_pad)), n_inner, axis=0)
    out_h = mesh_run(shape, axes_names, "hier_gtopk", g, e, r2)
    out_p = mesh_run(shape, axes_names, "hierarchical", g, e, r2)
    for a, b, name in [(out_h[0], out_p[0], "agg"),
                       (out_h[1], out_p[1], "resid"),
                       (out_h[2], out_p[2], "resid2")]:
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    print("HIER_GTOPK OK")


if __name__ == "__main__":
    {"eq2": check_eq2, "dense": check_dense, "gtopk": check_gtopk,
     "multipod": check_multipod, "adaptk": check_adaptk,
     "rtopk": check_rtopk, "bucketed": check_bucketed,
     "chunked": check_chunked, "serve": check_serve,
     "hier_gtopk": check_hier_gtopk}[sys.argv[1]]()
