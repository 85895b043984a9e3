"""What a cell is, read from data: ``BENCHMARK.json`` names the cell's
configuration and job; each lives in a file of its own, found by name:

* ``bench/configs/<config>.json``  the model (``model``) and its cut;
* ``bench/jobs/<traffic>.json``    mesh, batch, sequence, compressor,
                                   optimizer, and the steps traced;
* ``bench/limits/<workload>.json`` the limits of the comparison that
                                   decides ``correct``;
* ``bench/metrics/<name>.py``      one reader per per-layer metric.
"""
from __future__ import annotations

import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
HERE = ROOT / "bench"


def _read(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def job_of(raw: dict) -> dict:
    """The job with what follows from its mesh filled in."""
    job = dict(raw)
    job["workers"], job["model_size"] = (int(x) for x in raw["mesh"])
    return job


def load(workload: str, root: Path = ROOT) -> dict:
    """Everything a run of ``workload`` needs.  Raises KeyError for a
    workload that ``BENCHMARK.json`` does not list."""
    bench = _read(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = _read(root / configs[cell["config"]]["file"])
    here = root / "bench"
    job = job_of(_read(here / "jobs" / f"{cell['traffic']}.json"))
    if job["workers"] * job["model_size"] != cell["chips"]:
        raise ValueError(f"{workload}: mesh {job['mesh']} does not use the "
                         f"cell's {cell['chips']} chips")

    def listed(m):
        return workload in m.get("workloads", [workload])

    return {
        "name": workload,
        "chips": cell["chips"],
        "model": conf["model"],
        "job": job,
        "limits": _read(here / "limits" / f"{workload}.json")["limits"],
        "end_to_end": [m for m in bench["end_to_end"] if listed(m)],
        "per_layer": [m for m in bench["per_layer"] if listed(m)],
        "metrics_dir": here / "metrics",
    }
