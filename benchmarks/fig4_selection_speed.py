"""Paper Fig. 4: selection-operator compute cost vs dimension — extended
with the fused error-feedback pipeline (DESIGN.md §8).

The paper times Top_k / DGC_k / Gaussian_k on a V100; this container is
CPU, so wall-clock here is a PROXY — the structural claims that transfer
are (a) the cost hierarchy: Gaussian_k (O(d) elementwise, no sort) beats
DGC_k beats exact Top_k, and (b) the HBM-pass count of the Eq.-2
compression hot path: the fused pipeline (one moments pass, one
multi-threshold count pass, one compact+residual pass) versus the
unfused composition of the same kernels (~8-9 leaf-sized passes).

The module CLI (``--json``, used by the CI ``perf`` job) emits
``BENCH_fig4.json`` (schema ``fig4/v1``: rows of
``{shape, method, passes, ms}``), gated against
``benchmarks/baselines/fig4.json`` via ``tools/check_perf.py``; the
harness ``run()`` entry only reports rows so local benchmark sweeps
never overwrite the committed reference artifact.  Pass counts for the kernel pipelines are
measured by tracing the pipeline under ``ef_fused.count_passes``; the
pure-jnp reference has no kernel pass accounting (``passes: null``).
"""
from __future__ import annotations

import argparse
import json

import jax

from benchmarks.common import stamp_meta, timeit
from repro.core import compress_with_ef, get_compressor
from repro.kernels.ef_fused import (choose_block, count_passes,
                                    fused_compress_ef, unfused_compress_ef)
from repro.kernels.histk import histk_select_kernel

BENCH_JSON = "BENCH_fig4.json"
SCHEMA = "fig4/v1"

# (selection-speed ds, EF-pipeline ds) per mode; 2^22 is the acceptance
# shape for the fused-vs-unfused CPU wall-time claim.  The smoke run
# uses the paper's delta x10 (k = d/100): at tiny d the per-block
# expected counts otherwise fall below the staging floor and the
# fused-vs-unfused margin degenerates into timer noise — the CI gate
# needs the compaction-dominated regime the full shapes are in.
_SELECT_DS = {False: (1_000_000, 4_000_000, 8_000_000),
              True: (250_000,)}
_EF_DS = {False: (2 ** 20, 2 ** 22), True: (2 ** 16, 2 ** 18)}
_EF_KDIV = {False: 1000, True: 100}


def _selection_rows(smoke: bool):
    rows = []
    for d in _SELECT_DS[smoke]:
        u = jax.random.normal(jax.random.PRNGKey(0), (d,)) * 0.01
        k = max(1, d // 1000)
        key = jax.random.PRNGKey(1)
        times = {}
        for name in ("topk", "gaussiank", "dgck", "trimmedk"):
            spec = get_compressor(name)
            fn = jax.jit(lambda u, kk, s=spec: s.select(u, k, kk))
            times[name] = timeit(fn, u, key, warmup=1, iters=2)
            rows.append((f"fig4/{name}/d={d}", round(times[name], 1),
                         f"k={k}"))
        # beyond-paper histogram selector (interpreter-sized blocks —
        # the fixed 2048-lane tile is quadratic under interpret mode)
        blk = choose_block(d)
        fn = jax.jit(lambda u: histk_select_kernel(u, k, block=blk))
        times["histk"] = timeit(fn, u, warmup=1, iters=2)
        rows.append((f"fig4/histk/d={d}", round(times["histk"], 1),
                     f"k={k};beyond-paper"))
        rows.append((f"fig4/speedup/d={d}", 0.0,
                     f"gaussiank_vs_topk="
                     f"{times['topk'] / times['gaussiank']:.2f}x"))
    return rows


def _ef_pipeline_rows(smoke: bool):
    """Fused vs unfused EF compression: measured passes + wall time."""
    rows, bench = [], []
    iters = 2 if smoke else 3
    for d in _EF_DS[smoke]:
        k = max(1, d // _EF_KDIV[smoke])
        g = jax.random.normal(jax.random.PRNGKey(2), (d,)) * 0.02
        e = jax.random.normal(jax.random.PRNGKey(3), (d,)) * 0.01
        for comp in ("gaussiank", "histk"):
            for method, fn in (("fused", fused_compress_ef),
                               ("unfused", unfused_compress_ef)):
                with count_passes() as log:
                    jax.block_until_ready(fn(g, e, comp, k))
                jfn = jax.jit(lambda g, e, f=fn, c=comp: f(g, e, c, k))
                ms = timeit(jfn, g, e, warmup=1, iters=iters) / 1e3
                bench.append({"shape": d, "method": f"{comp}-{method}",
                              "passes": log.total(), "ms": round(ms, 3)})
                rows.append((f"fig4/ef-{comp}-{method}/d={d}",
                             round(ms * 1e3, 1),
                             f"k={k};passes={log.total()}"))
        # pure-jnp oracle (no kernel pass accounting)
        spec = get_compressor("gaussiank")
        jfn = jax.jit(lambda g, e: compress_with_ef(g, spec, k, e=e,
                                                    backend="reference"))
        ms = timeit(jfn, g, e, warmup=1, iters=iters) / 1e3
        bench.append({"shape": d, "method": "gaussiank-jnp",
                      "passes": None, "ms": round(ms, 3)})
        rows.append((f"fig4/ef-gaussiank-jnp/d={d}", round(ms * 1e3, 1),
                     f"k={k}"))
    return rows, bench


def _dispatch_rows():
    """Collectives-per-step of the bucketed vs per-leaf aggregation
    (ISSUE 5): counted by tracing both shard_mapped pipelines over an
    AbstractMesh (no devices) and counting the wire primitives in the
    jaxpr — deterministic and machine-independent, so the CI gate pins
    the bucketed counts exactly (``passes`` = logical codec-pair
    messages; L -> 1 for allgather, L·log2(W) -> log2(W) for gTop-k)."""
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh
    from jax.sharding import PartitionSpec as P

    from repro.core import get_compressor
    from repro.core.compression import CompressionConfig
    from repro.dist import aggregate
    from repro.dist.layout import build_layout
    from repro.launch.hlo_cost import count_wire_collectives

    L, W, msize, ratio = 8, 4, 2, 0.01
    params = {f"layer{i}": jnp.zeros((64 + 8 * i,)) for i in range(L)}
    spec = get_compressor("topk")
    layout = build_layout(params, msize, ratio, spec)
    grads = jax.tree.map(jnp.zeros_like, params)
    resid = aggregate.init_residuals(params, msize)
    flat = jnp.zeros((layout.flat_size,))
    mesh = AbstractMesh((W, msize), ("data", "model"))

    rows, bench = [], []
    for strategy in ("allgather", "gtopk"):
        config = CompressionConfig(compressor="topk", ratio=ratio,
                                   strategy=strategy, backend="reference")

        def per_leaf(g, e, config=config):
            return aggregate.aggregate_compressed(
                g, e, config, ("data",), "model", msize,
                jax.random.PRNGKey(0), world=W).agg

        def bucketed(g, e, config=config):
            return aggregate.aggregate_bucketed(
                g, e, layout, config, ("data",), "model",
                jax.random.PRNGKey(0), world=W).agg

        for method, fn, e_in in (("dispatch-perleaf", per_leaf, resid),
                                 ("dispatch-bucketed", bucketed, flat)):
            sm = jax.shard_map(fn, mesh=mesh, in_specs=(P(), P()),
                               out_specs=P(), axis_names={"data"},
                               check_vma=False)
            msgs = count_wire_collectives(
                jax.make_jaxpr(sm)(grads, e_in))["messages"]
            shape = f"L{L}-W{W}-{strategy}"
            bench.append({"shape": shape, "method": method,
                          "passes": msgs, "ms": 0.0})
            rows.append((f"fig4/{method}/{shape}", 0.0,
                         f"collectives={msgs}"))
    return rows, bench


def collect(smoke: bool = False):
    rows = _selection_rows(smoke)
    ef_rows, bench = _ef_pipeline_rows(smoke)
    d_rows, d_bench = _dispatch_rows()
    return (rows + ef_rows + d_rows,
            stamp_meta({"schema": SCHEMA, "smoke": smoke,
                        "rows": bench + d_bench}))


def run(smoke: bool = False):
    # harness entry point: report only — the committed ./BENCH_fig4.json
    # is a reference measurement, rewritten solely by an explicit
    # `python -m benchmarks.fig4_selection_speed --json ...` (the CI
    # perf job writes to its own workspace and uploads an artifact)
    rows, data = collect(smoke)
    rows.append((f"fig4/{BENCH_JSON}", 0.0,
                 f"rows={len(data['rows'])};smoke={smoke};not-written"))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes (CI perf job)")
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
