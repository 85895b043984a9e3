"""What a cell is, read from data: ``BENCHMARK.json`` names the cell's
configuration and job; each lives in a file of its own, found by name:

* ``bench/configs/<config>.json``  the model (``model``), its cut, and
                                   its family (``family``);
* ``bench/families/<family>.py``   the architecture's plain reference:
                                   ``param_shapes(model)``,
                                   ``loss(params, tokens, labels, model)``
                                   and ``forward_flops_per_token(model,
                                   seq)``;
* ``bench/jobs/<traffic>.json``    mesh, batch, sequence, compressor,
                                   optimizer, and the steps traced;
* ``bench/limits/<workload>.json`` the limits of the comparison that
                                   decides ``correct``;
* ``bench/metrics/<name>.py``      one reader per per-layer metric.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
HERE = ROOT / "bench"
FAMILY_API = ("param_shapes", "loss", "forward_flops_per_token")
_MODULE_NAME = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")


def _read(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def job_of(raw: dict) -> dict:
    """The job with what follows from its mesh filled in."""
    job = dict(raw)
    job["workers"], job["model_size"] = (int(x) for x in raw["mesh"])
    return job


def module(path: Path, prefix: str):
    """The Python file at ``path``, loaded as a module of its own."""
    spec = importlib.util.spec_from_file_location(
        prefix + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(name, here: Path = HERE):
    """The family ``bench/families/<name>.py``.  Raises for a name that
    is not a module name, a file that is not there, or a module that
    lacks a function of ``FAMILY_API``."""
    if not isinstance(name, str) or not _MODULE_NAME.match(name):
        raise ValueError(f"{name!r} is not a family name")
    path = here / "families" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no family {name!r}: {path} is not there")
    mod = module(path, "bench_family_")
    missing = [f for f in FAMILY_API if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(f"family {name!r} lacks {missing}")
    return mod


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
    if "family" not in conf:
        raise KeyError(f"{cell['config']}: the configuration names no "
                       "family")
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
        "family": family(conf["family"], here),
        "job": job,
        "limits": _read(here / "limits" / f"{workload}.json")["limits"],
        "end_to_end": [m for m in bench["end_to_end"] if listed(m)],
        "per_layer": [m for m in bench["per_layer"] if listed(m)],
        "metrics_dir": here / "metrics",
    }
