"""Chunked overlapped schedule benchmark (DESIGN.md §11, CI ``perf``).

Two row families, emitted to ``BENCH_overlap.json`` (schema
``overlap/v1``, gated by ``tools/check_perf.py --overlap-*`` against
``benchmarks/baselines/overlap.json``):

* ``dispatch-chunked{N}`` — collectives-per-step of the chunked
  aggregation at N chunks, counted by tracing the shard_mapped pipeline
  over an AbstractMesh and counting wire primitives in the jaxpr.
  Deterministic and machine-independent; the gate pins them exactly and
  checks the structural law ``messages(N) == N * messages(1)`` per
  strategy (N all-gathers for allgather, 2N for hierarchical,
  N*log2(W) gTop-k rounds).
* ``step-unchunked`` / ``step-chunked`` — wall time of a real 8-host-
  device train step at ``--chunks 1`` vs ``--chunks 4``.  On CPU there
  are no async collectives, so the overlap cannot WIN here; the gate
  checks the other direction — chunking must not regress the step
  beyond a tolerance (the schedule stays free on the hardware where it
  pays, and a slowdown here means per-chunk dispatch overhead crept
  in).

Run via the harness (``python -m benchmarks.run overlap --smoke``) or
directly (``python -m benchmarks.overlap_schedule --smoke --json
BENCH_overlap.json``); both give this module its own process, so the
device-count flag below lands before jax initialises.
"""
from __future__ import annotations

import argparse
import json
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

BENCH_JSON = "BENCH_overlap.json"
SCHEMA = "overlap/v1"
CHUNKS = (1, 2, 4)
STEP_CHUNKS = 4


def _dispatch_rows():
    """jaxpr-counted collectives per step for chunks in CHUNKS, all
    three strategies (AbstractMesh — no devices needed)."""
    from jax.sharding import AbstractMesh
    from jax.sharding import PartitionSpec as P

    from repro.core import get_compressor
    from repro.core.compression import CompressionConfig
    from repro.dist import aggregate
    from repro.dist.layout import build_chunk_plan, build_layout
    from repro.launch.hlo_cost import count_wire_collectives

    L, W, msize, ratio = 6, 8, 1, 0.02
    params = {f"layer{i}": jnp.zeros((96 + 16 * i,)) for i in range(L)}
    spec = get_compressor("topk")
    layout = build_layout(params, msize, ratio, spec)
    grads = jax.tree.map(jnp.zeros_like, params)
    flat = jnp.zeros((layout.flat_size,))
    flat_mesh = AbstractMesh((W, msize), ("data", "model"))
    pod_mesh = AbstractMesh((2, W // 2, msize), ("pod", "data", "model"))
    cases = (
        ("allgather", flat_mesh, ("data",), False),
        ("hierarchical", pod_mesh, ("pod", "data"), True),
        ("gtopk", flat_mesh, ("data",), False),
    )
    rows, bench = [], []
    for strategy, mesh, data_axes, with_r2 in cases:
        config = CompressionConfig(compressor="topk", ratio=ratio,
                                   strategy=strategy, backend="reference")
        for n in CHUNKS:
            plan = build_chunk_plan(layout, n)

            def agg_fn(g, e, *r2s, plan=plan, config=config,
                       data_axes=data_axes):
                return aggregate.aggregate_bucketed_chunked(
                    g, e, layout, plan, config, data_axes, "model",
                    jax.random.PRNGKey(0), world=W,
                    resid2=r2s[0] if r2s else None).agg

            n_in = 3 if with_r2 else 2
            sm = jax.shard_map(
                agg_fn, mesh=mesh, in_specs=(P(),) * n_in, out_specs=P(),
                axis_names=set(data_axes), check_vma=False)
            args = (grads, flat) + ((flat,) if with_r2 else ())
            msgs = count_wire_collectives(jax.make_jaxpr(sm)(*args))[
                "messages"]
            shape = f"L{L}-W{W}-{strategy}"
            bench.append({"shape": shape, "method": f"dispatch-chunked{n}",
                          "passes": msgs, "ms": 0.0})
            rows.append((f"overlap/dispatch-chunked{n}/{shape}", 0.0,
                         f"collectives={msgs}"))
    return rows, bench


def _step_rows(smoke: bool):
    """Real-device step wall time, chunked vs unchunked, on the largest
    power-of-two data world the host exposes (8 under the CI flag)."""
    from benchmarks.common import timeit
    from repro.core import get_compressor
    from repro.core.compression import CompressionConfig
    from repro.dist.layout import build_layout
    from repro.launch.mesh import make_mesh
    from repro.optim import constant, sgd_momentum
    from repro.train import init_train_state, make_train_step

    ndev = len(jax.devices())
    W = 1 << (ndev.bit_length() - 1)
    d = 4096 if smoke else 65536
    L, ratio = 8, 0.01
    key = jax.random.PRNGKey(0)
    params = {f"layer{i}": 0.01 * jax.random.normal(
        jax.random.fold_in(key, i), (d + 128 * i,)) for i in range(L)}
    layout = build_layout(params, 1, ratio, get_compressor("topk"))
    mesh = make_mesh((W, 1), ("data", "model"))
    opt = sgd_momentum(0.9)

    def loss_fn(p, b):
        l = sum(jnp.sum((leaf * b["x"][0, 0]) ** 2)
                for leaf in jax.tree.leaves(p))
        return l, {"loss": l}

    batch = {"x": jnp.ones((W, 1))}
    iters = 3 if smoke else 10
    rows, bench = [], []
    times = {}
    for n_chunks, method in ((1, "step-unchunked"),
                             (STEP_CHUNKS, "step-chunked")):
        step = make_train_step(
            None, mesh, opt, constant(0.1),
            compression=CompressionConfig(compressor="topk", ratio=ratio,
                                          chunks=n_chunks),
            loss_fn=loss_fn, layout=layout)
        state = init_train_state(params, opt, workers=W, model_size=1,
                                 layout=layout)
        _, m = step(state, batch)  # compile
        coll = int(m["collectives_per_step"])
        ms = timeit(step, state, batch, warmup=1, iters=iters) / 1e3
        shape = f"L{L}-W{W}-allgather-d{d}"
        times[method] = ms
        bench.append({"shape": shape, "method": method, "passes": coll,
                      "ms": round(ms, 3)})
        rows.append((f"overlap/{method}/{shape}", round(ms * 1e3, 1),
                     f"chunks={n_chunks};collectives={coll}"))
    ratio_t = times["step-chunked"] / times["step-unchunked"]
    rows.append((f"overlap/step-ratio/L{L}-W{W}", 0.0,
                 f"chunked_vs_unchunked={ratio_t:.3f}x"))
    return rows, bench


def collect(smoke: bool = False):
    # lazy: benchmarks.common imports jax, which must happen after this
    # module's XLA_FLAGS setdefault
    from benchmarks.common import stamp_meta

    d_rows, d_bench = _dispatch_rows()
    s_rows, s_bench = _step_rows(smoke)
    return (d_rows + s_rows,
            stamp_meta({"schema": SCHEMA, "smoke": smoke,
                        "rows": d_bench + s_bench}))


def run(smoke: bool = False):
    # harness entry point: report only — the committed baseline is
    # rewritten solely by an explicit --json + check_perf --update
    rows, data = collect(smoke)
    rows.append((f"overlap/{BENCH_JSON}", 0.0,
                 f"rows={len(data['rows'])};smoke={smoke};not-written"))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes / few iters (CI perf job)")
    ap.add_argument("--json", default=BENCH_JSON,
                    help=f"output path (default: {BENCH_JSON})")
    args = ap.parse_args(argv)
    rows, data = collect(args.smoke)
    for r in rows:
        print(",".join(str(x) for x in r), flush=True)
    with open(args.json, "w") as f:
        json.dump(data, f, indent=1)
    print(f"wrote {args.json} ({len(data['rows'])} rows)")


if __name__ == "__main__":
    main()
