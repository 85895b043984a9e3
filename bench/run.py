#!/usr/bin/env python3
"""Benchmark of compressed data-parallel training on TPU chips.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for.  ``BENCHMARK.json`` lists the cells; ``bench/spec.py``
says where each cell's configuration, job and limits live.  With
``--trace 0`` the last line of standard output is one JSON object with
the cell's end-to-end metrics; with ``--trace 1`` it has the per-layer
metrics read from a profiler trace of ``trace_steps`` steps.  The
numbers that decide ``correct`` are printed, each beside its limit, as
the last lines of standard error and under ``checks``, the last key of
that object.  Without a TPU, or with fewer chips than the cell asks
for, it prints no result and exits with code 3.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
NO_DEVICE = 3


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not 0 <= args.seed < 2 ** 32:
        ap.error("--seed must be a whole number in [0, 2**32)")
    return args


def compile_cache() -> str:
    """JAX's persistent compilation cache where the program keeps it
    (``repro.launch.env.enable_compile_cache``: ``JAX_COMPILATION_CACHE_DIR``,
    else ``.jax_cache`` in the checkout), with every program cached,
    however quickly it compiled."""
    import jax
    from repro.launch.env import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import spec

    cell = spec.load(args.workload, ROOT)
    compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s). "
              "Nothing was run.", file=sys.stderr)
        return NO_DEVICE
    from bench import harness

    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      t0=T0)
    checks = out.pop("checks")
    for line in out.pop("leaves"):
        print(f"leaf {line}", file=sys.stderr)
    for name, value, limit in checks:
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    out["checks"] = {name: {"value": value, "limit": limit}
                     for name, value, limit in checks}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
