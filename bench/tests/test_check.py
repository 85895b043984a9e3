"""The comparison that decides ``correct`` passes a sound run and fails
the control and every fault the cell can have, with the cell's own
limits: a run of the harness on a small stand-in of each cell, with the
chip check skipped and the timed path broken underneath."""
import time

import pytest

from bench import harness
from bench.tests import tiny

FAULTS = {
    "stablelm_efjnp_1chip": ("control", "unchanged", "half_batch", "answer"),
}
CASES = [(c, None) for c in FAULTS] + [(c, f) for c, fs in FAULTS.items()
                                       for f in fs]


@pytest.mark.parametrize("name,fault", CASES)
def test_check(name, fault):
    out = harness.run(tiny.cell(name), 2 ** 31 + 11, 0.2, False,
                      t0=time.perf_counter(), fault=fault)
    assert out["correct"] is (fault is None), out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in
                                   tiny.cell(name)["end_to_end"]}
