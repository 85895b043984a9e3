#!/usr/bin/env python
"""Spawn a real multi-process jax mesh and run the tuner validation.

Two legs (see src/repro/launch/multihost.py for what each asserts):

1. **coordinate** — spawn N ``repro.launch.multihost --mode coordinate``
   processes against a local coordinator and require every one to print
   ``COORDINATE OK``: jax.distributed really federates N processes on
   this machine.  Computation stays per-process because the CPU backend
   refuses multiprocess computations; on an accelerator fleet the same
   processes would run the mesh for real.
2. **validate** — one process with the mesh's worth of forced host
   devices runs ``--mode validate``: measured topology -> tuner
   predictions -> measured collective patterns, asserting the chosen
   strategy's predicted wire time lands within --factor of measured and
   that the predicted ranking matches the measured ranking for every
   pair the model separates beyond its accuracy claim.

Usage (the slow CI `multihost` job):

  PYTHONPATH=src python tools/launch_multihost.py \
      --processes 2 --meshes 2x2x2,8x1 --json multihost_report.json
"""
import argparse
import json
import math
import os
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _env(extra_xla: str = ""):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    # the children are a CPU federation rehearsal run side by side: none
    # of them may reach for an accelerator, which one process owns
    env["JAX_PLATFORMS"] = "cpu"
    if extra_xla:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + extra_xla).strip()
    return env


def run_coordinate(processes: int, local_devices: int, timeout: int) -> list:
    port = _free_port()
    cmd_base = [sys.executable, "-m", "repro.launch.multihost",
                "--mode", "coordinate",
                "--coordinator", f"127.0.0.1:{port}",
                "--num-processes", str(processes)]
    procs = []
    for pid in range(processes):
        procs.append(subprocess.Popen(
            cmd_base + ["--process-id", str(pid)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            cwd=REPO,
            env=_env(f"--xla_force_host_platform_device_count="
                     f"{local_devices}")))
    outs = []
    ok = True
    for pid, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
            out += "\n[TIMEOUT]"
        outs.append(out)
        if p.returncode != 0 or f"COORDINATE OK p{pid}" not in out:
            ok = False
            print(f"-- coordinate p{pid} FAILED (rc={p.returncode}) --")
            print(out)
    if not ok:
        raise SystemExit("coordinate leg failed")
    print(f"coordinate leg OK: {processes} processes x {local_devices} "
          f"local devices federated")
    return outs


def run_validate(mesh: str, factor: float, loose_factor: float,
                 json_out: str, timeout: int) -> dict:
    need = math.prod(int(x) for x in mesh.split("x"))
    cmd = [sys.executable, "-m", "repro.launch.multihost",
           "--mode", "validate", "--mesh", mesh,
           "--factor", str(factor), "--loose-factor", str(loose_factor)]
    if json_out:
        cmd += ["--json", json_out]
    p = subprocess.run(
        cmd, cwd=REPO, timeout=timeout, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        env=_env(f"--xla_force_host_platform_device_count={need}"))
    print(p.stdout)
    if p.returncode != 0 or f"VALIDATE OK mesh={mesh}" not in p.stdout:
        raise SystemExit(f"validate leg failed on mesh {mesh} "
                         f"(rc={p.returncode})")
    return json.load(open(json_out)) if json_out else {}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--processes", type=int, default=2,
                    help="process count for the coordinate leg")
    ap.add_argument("--local-devices", type=int, default=2,
                    help="forced host devices per coordinate process")
    ap.add_argument("--meshes", default="2x2x2,8x1",
                    help="comma-separated mesh shapes for the validate leg")
    ap.add_argument("--factor", type=float, default=2.0)
    ap.add_argument("--loose-factor", type=float, default=4.0)
    ap.add_argument("--timeout", type=int, default=900,
                    help="seconds per leg")
    ap.add_argument("--json", default="",
                    help="write the combined report here")
    ap.add_argument("--skip-coordinate", action="store_true")
    ap.add_argument("--skip-validate", action="store_true")
    args = ap.parse_args(argv)

    report = {"coordinate": None, "validate": []}
    if not args.skip_coordinate:
        run_coordinate(args.processes, args.local_devices, args.timeout)
        report["coordinate"] = {"processes": args.processes,
                                "local_devices": args.local_devices,
                                "ok": True}
    if not args.skip_validate:
        for mesh in [m for m in args.meshes.split(",") if m]:
            sub = (args.json + f".{mesh}.json") if args.json else ""
            rep = run_validate(mesh, args.factor, args.loose_factor,
                               sub, args.timeout)
            report["validate"].append(rep or {"mesh": mesh, "ok": True})
            if sub and os.path.exists(sub):
                os.remove(sub)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)
        print(f"wrote {args.json}")
    print("MULTIHOST OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
