#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds <n> [<n> ...] \
        [--faults control half_batch answer fused] [--fault-seeds 3]

For each seed, in one process: the program's first steps (the sound
run), the reference's, and the same first steps with each fault planted
(in the program, or the control: the reference in bfloat16 put in its
place), each compared with the reference.  ``fused`` runs the program
with its fused error-feedback kernels.  There is no measured window: the readings are those
of set-up, which a run of ``run.py`` makes the same way.  One JSON line
per seed and fault goes to standard output.  Not part of a benchmark
run.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="*", default=["control"])
    ap.add_argument("--fault-seeds", type=int, default=3,
                    help="plant the faults on the first N seeds only")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import run, spec

    cell = spec.load(args.workload, ROOT)
    run.compile_cache()
    import jax

    from bench import compare, harness, reference, system

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return run.NO_DEVICE
    model, job, family = cell["model"], cell["job"], cell["family"]
    n = job["check_steps"]
    names = reference.leaf_shapes(family, model)[0]
    for n_seed, seed in enumerate(args.seeds):
        t = time.perf_counter()
        ref = reference.first_steps(family, model, job, seed, n_steps=n)
        t_ref = time.perf_counter() - t
        faults = args.faults if n_seed < args.fault_seeds else []
        for fault in [None] + faults:
            t = time.perf_counter()
            if fault == harness.CONTROL:
                prog = harness.control(cell, seed)
            else:
                sut = system.System(model, job, seed, fault=fault)
                state, prog, _ = harness.first_steps(sut, n)
                del sut, state
                gc.collect()
            read = compare.readings(prog, ref)
            print(json.dumps({
                "workload": args.workload, "seed": seed,
                "run": fault or "sound", **read,
                "loss": prog["loss"], "ref_loss": ref["loss"],
                "leaves": names,
                **{f"{side}_{k}": [float(x) for x in d[k]]
                   for side, d in (("prog", prog), ("ref", ref))
                   for k in ("update_norms", "update_counts",
                             "first_change_norms", "change_norms")},
                "ref_grad_norms": [float(x) for x in ref["grad_norms"]],
                "seconds": time.perf_counter() - t,
                "reference_seconds": t_ref}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
