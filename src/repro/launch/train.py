"""Training driver.

Examples (CPU container — force host devices before jax import):

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
      --host-devices 8 --mesh 4x2 --compressor gaussiank --ratio 0.001 \
      --steps 50 --batch 8 --seq 128

  # production launch (real TPU pod; mesh resolved from the platform)
  PYTHONPATH=src python -m repro.launch.train --arch phi3.5-moe-42b-a6.6b \
      --mesh 16x16 --compressor gaussiank --steps 1000
"""
from __future__ import annotations

import argparse
import os
import sys
import time

# the steps [a, b) a --profile-dir trace holds, after the warm-up steps
PROFILE_STEPS = (2, 5)


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke variant of the arch")
    ap.add_argument("--compressor", default="gaussiank",
                    help="none|topk|randk|gaussiank|gaussiank2|dgck|"
                         "trimmedk|histk|rtopk")
    ap.add_argument("--ratio", type=float, default=0.001)
    ap.add_argument("--strategy", default="allgather",
                    choices=["allgather", "gtopk", "hierarchical",
                             "hier_gtopk", "auto"],
                    help="sparse wire pattern: flat all-gather (O(P) "
                         "pairs), gTop-k recursive doubling (O(log P), "
                         "power-of-two data axes), two-level pod "
                         "reduction, the pod-gather + cross-pod gTop-k "
                         "hybrid, or 'auto' — pick per mesh axis from "
                         "the alpha-beta topology model (dist/tuner.py, "
                         "DESIGN.md §14)")
    ap.add_argument("--hierarchical", action="store_true",
                    help="deprecated alias for --strategy hierarchical")
    ap.add_argument("--topology", default="",
                    help="JSON topology descriptor (launch/topo.py "
                         "schema: per-axis alpha/beta links + hardware "
                         "spec) used by --strategy auto; default: "
                         "measure the live mesh with the startup "
                         "ping/ramp microbenchmark")
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "fused", "reference"],
                    help="compression pipeline: fused single-pass Pallas "
                         "kernels (DESIGN.md §8) when the compressor "
                         "supports them, or the jnp reference")
    ap.add_argument("--pipeline", default="bucketed",
                    choices=["bucketed", "perleaf"],
                    help="aggregation dispatch (DESIGN.md §10): the flat "
                         "bucketed pipeline (one wire collective per "
                         "level per step; residuals stored as one flat "
                         "buffer) or the legacy per-leaf loop (one "
                         "collective chain per gradient leaf) — results "
                         "are bit-identical")
    ap.add_argument("--chunks", type=int, default=1,
                    help="split the bucketed wire block into N leaf-"
                         "aligned chunk groups and issue one collective "
                         "chain per chunk as the backward pass releases "
                         "its grads (DESIGN.md §11) — overlaps wire with "
                         "compute at N collectives per level; 1 = the "
                         "unchunked schedule; results are bit-identical "
                         "for any N (needs --pipeline bucketed and a "
                         "sparse compressor)")
    ap.add_argument("--density-policy", default="",
                    choices=["", "none", "uniform", "variance", "absmax"],
                    help="adaptive layer-wise density (DESIGN.md §9): "
                         "redistribute the global k budget across leaves "
                         "each step from the fused pass-A moments; "
                         "default: the arch config's density_policy, "
                         "else fixed-k")
    ap.add_argument("--density-floor", type=float, default=0.25,
                    help="per-leaf floor clamp as a multiple of the "
                         "fixed-k share")
    ap.add_argument("--density-ceil", type=float, default=4.0,
                    help="per-leaf ceiling clamp (sizes the static codec "
                         "capacity / wire volume)")
    ap.add_argument("--density-ema", type=float, default=0.0,
                    help="EMA over the allocation signal (0 = stateless)")
    ap.add_argument("--density-warmup", type=int, default=0,
                    help="DGC-style exponential density warmup steps")
    ap.add_argument("--density-warmup-mult", type=float, default=16.0,
                    help="warmup start multiplier on the global budget")
    ap.add_argument("--global-k-policy", default="none",
                    choices=["none", "normdecay"],
                    help="convergence-aware global-k controller (DESIGN.md "
                         "§12): normdecay scales the global element budget "
                         "by the estimated gradient-norm decay "
                         "sqrt(EMA[grad-norm²]/first-norm²); needs an "
                         "adaptive --density-policy")
    ap.add_argument("--global-k-ema", type=float, default=0.9,
                    help="EMA factor over the controller's norm estimate")
    ap.add_argument("--global-k-floor", type=float, default=0.25,
                    help="lowest budget scale the controller may reach")
    ap.add_argument("--publish-every", type=int, default=0,
                    help="publish a compressed weight delta for serving "
                         "replicas every N steps (serve/publish.py, "
                         "DESIGN.md §13); 0 = no publishing")
    ap.add_argument("--publish-ratio", type=float, default=0.01,
                    help="density of the publish delta stream (top-k over "
                         "params - published view)")
    ap.add_argument("--resync-every", type=int, default=8,
                    help="every Nth publish ships the dense bucket: "
                         "replica == trainer exactly at those epochs")
    ap.add_argument("--optimizer", default="sgd",
                    choices=["sgd", "adamw"])
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--schedule", default="constant",
                    choices=["constant", "cosine", "step"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="4x2",
                    help="DxM or PxDxM, e.g. 4x2 or 2x2x2")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force N host CPU devices (testing)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint", default="",
                    help="path to save the final state (npz)")
    ap.add_argument("--resume", default="")
    ap.add_argument("--profile-dir", default="",
                    help="write a device trace of the steps "
                         f"[{PROFILE_STEPS[0]}, {PROFILE_STEPS[1]}) that "
                         "--steps reaches here (jax.profiler), "
                         "with the compiled step's HLO text as "
                         "step.hlo.txt; `python3 bench/scopes.py DIR "
                         "--steps N` splits the step's device time by its "
                         "named scopes (DESIGN.md §16)")
    return ap.parse_args(argv)


def main(argv=None):
    train(argv)
    return 0


def train(argv=None) -> list:
    """Run the training loop; returns one record per logged step: ``step``,
    ``loss``, ``comm_frac``, ``ef_leaves_at_cap`` and
    ``ef_leaves_under_band``, each None where the step has no such
    metric (``comm_frac`` for dense, the counters off the bucketed
    pipeline).  The loop's host spans (``train.input``,
    ``train.dispatch``, ``train.sync``) label a ``--profile-dir``
    trace."""
    args = parse_args(argv)
    prof_lo, prof_hi = PROFILE_STEPS[0], min(PROFILE_STEPS[1], args.steps)
    if args.profile_dir and prof_hi <= prof_lo:
        raise SystemExit(f"--profile-dir traces the steps [{prof_lo}, "
                         f"{PROFILE_STEPS[1]}), after the warm-up: it needs "
                         f"--steps > {prof_lo}, got {args.steps}")
    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices}")

    from repro.launch.env import enable_compile_cache

    enable_compile_cache()
    import jax

    from repro.checkpoint import load_state, save_state
    from repro.configs import get_config
    from repro.data import batch_for
    from repro.launch.mesh import (data_world_size, make_mesh,
                                   model_axis_size)
    from repro.models import init_params
    from repro.optim import adamw, constant, cosine, sgd_momentum, step_decay
    from repro.train import init_train_state, make_train_step

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    dims = tuple(int(x) for x in args.mesh.split("x"))
    axes = ("pod", "data", "model")[-len(dims):]
    mesh = make_mesh(dims, axes)

    opt = sgd_momentum(0.9) if args.optimizer == "sgd" else adamw()
    lr_fn = {"constant": lambda: constant(args.lr),
             "cosine": lambda: cosine(args.lr, args.steps),
             "step": lambda: step_decay(args.lr, 0.1,
                                        max(args.steps // 2, 1))}[
        args.schedule]()

    from repro.dist.aggregate import resolve_strategy

    strategy = (args.strategy if args.strategy == "auto"
                else resolve_strategy(args.strategy, args.hierarchical))
    from repro.core.adaptk import DYNAMIC_COMPRESSORS, make_policy

    # an explicit --density-policy always wins (and a non-dynamic
    # compressor then fails loudly in dist/aggregate); the arch-config
    # DEFAULT only applies where adaptive density is supported, so e.g.
    # `--compressor dgck` keeps training fixed-k as before
    pol_name = args.density_policy
    if not pol_name and args.compressor in DYNAMIC_COMPRESSORS:
        pol_name = cfg.density_policy
    policy = None
    if pol_name and pol_name != "none" and args.compressor != "none":
        policy = make_policy(
            pol_name, floor_mult=args.density_floor,
            ceil_mult=args.density_ceil, ema=args.density_ema,
            warmup_steps=args.density_warmup,
            warmup_mult=args.density_warmup_mult if args.density_warmup
            else 1.0,
            global_policy=args.global_k_policy,
            global_ema=args.global_k_ema,
            global_floor=args.global_k_floor)
    elif args.global_k_policy != "none":
        raise SystemExit(
            "--global-k-policy scales the adaptive global budget, so it "
            "needs an adaptive --density-policy (uniform|variance|absmax) "
            "and a sparse dynamic-k compressor")
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    layout = None
    if args.pipeline == "bucketed" and args.compressor != "none":
        from repro.core.compressors import get_compressor
        from repro.dist.layout import build_layout

        # computed ONCE from the param pytree: the static bucket geometry
        # behind the one-collective-per-level wire (DESIGN.md §10)
        layout = build_layout(params, model_axis_size(mesh), args.ratio,
                              get_compressor(args.compressor),
                              density_policy=policy)
    if args.chunks < 1:
        raise SystemExit(f"--chunks must be >= 1, got {args.chunks}")
    if args.chunks > 1 and layout is None:
        raise SystemExit(
            "--chunks > 1 needs the bucketed sparse pipeline: use "
            "--pipeline bucketed with a sparse compressor (the chunked "
            "schedule re-dispatches the flat wire block, DESIGN.md §11)")
    decision = None
    if strategy == "auto":
        if args.compressor == "none":
            raise SystemExit(
                "--strategy auto tunes the sparse wire pattern; it is "
                "meaningless with --compressor none (dense all-reduce)")
        from repro.core.compressors import get_compressor
        from repro.dist.layout import build_layout
        from repro.dist.tuner import choose_strategy
        from repro.launch.mesh import data_axes_of
        from repro.launch.topo import load_topology, measure_topology

        topo = (load_topology(args.topology) if args.topology
                else measure_topology(mesh))
        # the per-leaf pipeline has no layout of its own; the tuner only
        # needs the bucket geometry (payload/dense sizes), so build one
        tuner_layout = layout if layout is not None else build_layout(
            params, model_axis_size(mesh), args.ratio,
            get_compressor(args.compressor), density_policy=policy)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        data_axes = [(ax, sizes[ax]) for ax in data_axes_of(mesh)]
        decision = choose_strategy(tuner_layout, data_axes, topo)
        strategy = decision.strategy
        preds = " ".join(f"{p.strategy}={p.total_s * 1e6:.1f}us"
                         for p in decision.predictions)
        print(f"tuner: topology={decision.topology} "
              f"axes={dict(data_axes)} -> strategy={strategy} ({preds})")
    from repro.core.compression import CompressionConfig

    config = CompressionConfig(
        compressor=args.compressor, ratio=args.ratio, strategy=strategy,
        backend=args.backend, density_policy=policy, chunks=args.chunks)
    state = init_train_state(
        params, opt, workers=data_world_size(mesh),
        model_size=model_axis_size(mesh),
        compression=config, layout=layout)

    pub_state = pub_layout = pub_config = None
    if args.publish_every > 0:
        from repro.core.compressors import get_compressor
        from repro.dist.layout import build_layout, rebudget_layout
        from repro.serve import init_publisher_state

        pub_config = CompressionConfig(compressor="topk",
                                       ratio=args.publish_ratio,
                                       backend=args.backend)
        if layout is not None:
            # delta-layout reuse: same row geometry as the gradient wire,
            # codec capacities re-budgeted at the publish ratio
            pub_layout = rebudget_layout(layout, args.publish_ratio,
                                         get_compressor("topk"))
        else:
            pub_layout = build_layout(params, model_axis_size(mesh),
                                      pub_config)
        pub_state = init_publisher_state(pub_layout)

    if args.resume:
        # layout enables the per-leaf -> flat-bucket residual migration
        # shim for checkpoints written before the bucketed pipeline; the
        # publisher cursor rides under "publish/" (zero-filled when the
        # checkpoint predates it -> seq 0 forces a resync first)
        if pub_state is not None:
            full = load_state(args.resume, dict(state, publish=pub_state),
                              layout=layout)
            pub_state = full.pop("publish")
            state = full
        else:
            state = load_state(args.resume, state, layout=layout)

    step = make_train_step(cfg, mesh, opt, lr_fn, compression=config,
                           remat=not args.smoke, seed=args.seed,
                           layout=layout)
    # place the state as the step returns it, so step 1 reuses step 0's
    # executable instead of compiling again for new input shardings
    from jax.sharding import NamedSharding

    from repro.dist.sharding import train_state_specs
    from repro.launch.mesh import data_axes_of

    axes_d = data_axes_of(mesh)
    state = jax.device_put(state, jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        train_state_specs(state, axes_d if len(axes_d) > 1 else axes_d[0])))

    print(f"arch={cfg.name} compressor={args.compressor} ratio={args.ratio} "
          f"strategy={strategy}{'(auto)' if decision is not None else ''} "
          f"backend={args.backend} mesh={args.mesh} "
          f"pipeline={args.pipeline} chunks={args.chunks} "
          f"density_policy={pol_name or 'fixed-k'} "
          f"global_k={args.global_k_policy} steps={args.steps}")
    if pub_state is not None:
        from repro.serve import RESYNC, message_bits, publish
        pub_key = jax.random.fold_in(jax.random.PRNGKey(args.seed), 0x9B)
        pub_bits, n_deltas, n_resyncs = 0, 0, 0
    history = []
    span = jax.profiler.TraceAnnotation
    run = step
    t0 = time.time()
    for i in range(args.steps):
        if args.profile_dir and i == prof_lo:
            # the executable the traced steps run, whose text goes beside
            # the trace: the jit's own for these inputs (the last step's
            # shapes and shardings), so lowering it compiles nothing
            run = step.lower(state, batch).compile()
            jax.profiler.start_trace(args.profile_dir)
        with span("train.input"):
            batch = batch_for(cfg, i, global_batch=args.batch,
                              seq_len=args.seq, seed=args.seed)
        with span("train.dispatch"):
            state, m = run(state, batch)
        if pub_state is not None and (i + 1) % args.publish_every == 0:
            pub_state, msg = publish(pub_state, state["params"], pub_layout,
                                     pub_config, pub_key,
                                     resync_every=args.resync_every)
            pub_bits += message_bits(msg)
            if msg.kind == RESYNC:
                n_resyncs += 1
            else:
                n_deltas += 1
        if i % args.log_every == 0 or i == args.steps - 1:
            with span("train.sync"):
                loss = float(m["loss"])
            t_now = time.time()
            comm, r = "", None
            if "comm_bits_sparse" in m:
                r = float(m["comm_bits_sparse"]) / float(m["comm_bits_dense"])
                comm = f" comm_frac={r:.4f}"
            if "collectives_per_step" in m:
                comm += f" coll={int(m['collectives_per_step'])}"
            if "k_total" in m:
                comm += f" k_total={int(m['k_total'])}"
            # Algorithm 1's outcome over the leaf segments (DESIGN.md §16)
            outcome = {c: float(m[c]) if c in m else None
                       for c in ("ef_leaves_at_cap", "ef_leaves_under_band")}
            if outcome["ef_leaves_at_cap"] is not None:
                comm += (f" at_cap={outcome['ef_leaves_at_cap']:g}"
                         f" under_band={outcome['ef_leaves_under_band']:g}")
            # the expert layers' routing (DESIGN.md §17)
            if "moe_rows_held" in m:
                comm += (f" moe_rows_held={float(m['moe_rows_held']):g}"
                         f" moe_load_max={float(m['moe_load_max']):.4g}"
                         f" moe_rows_dropped="
                         f"{float(m['moe_rows_dropped']):g}")
            if decision is not None:
                # record the auto decision alongside the step metrics
                comm += (f" tuner={decision.strategy}"
                         f" pred_wire_us={decision.best.total_s * 1e6:.1f}")
            print(f"step {i:5d} loss={loss:.4f} "
                  f"lr={float(m['lr']):.4g}{comm} "
                  f"({t_now - t0:.1f}s)", flush=True)
            history.append({"step": i, "loss": loss, "comm_frac": r,
                            **outcome})
        if args.profile_dir and i == prof_hi - 1:
            jax.block_until_ready(state)
            jax.profiler.stop_trace()
            with open(os.path.join(args.profile_dir, "step.hlo.txt"),
                      "w") as f:
                f.write(run.as_text())
            run = step
            print(f"profile of steps {prof_lo}:{prof_hi} -> "
                  f"{args.profile_dir}")
    if pub_state is not None:
        print(f"published {n_deltas} deltas + {n_resyncs} resyncs "
              f"({pub_bits / 8 / 2 ** 20:.3f} MiB on the wire)")
    if args.checkpoint:
        save_state(args.checkpoint, dict(state, publish=pub_state)
                   if pub_state is not None else state)
        print(f"saved -> {args.checkpoint}")
    return history


if __name__ == "__main__":
    sys.exit(main())
