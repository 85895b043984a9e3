"""The command's contract that a CPU can check: no result without a TPU,
and every name in ``BENCHMARK.json`` resolves to its files."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench import compare, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _bench():
    with open(spec.ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "stablelm_efjnp_1chip",
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 3, p.stderr
    assert p.stdout == ""


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_cell_resolves(cell):
    c = spec.load(cell)
    assert NAME.match(cell)
    assert set(c["limits"]) == set(compare.NAMES)
    e2e = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c["per_layer"]
    for m in c["per_layer"]:
        assert m["moves"] in e2e
        assert (c["metrics_dir"] / f"{m['name']}.py").is_file()


def test_metrics_and_layers():
    b = _bench()
    e2e = {m["name"] for m in b["end_to_end"]}
    cells = {w["name"] for w in b["workloads"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        assert NAME.match(m["name"])
