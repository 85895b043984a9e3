"""The nine readers of the step's named layers (``bench/scopes.py``):
nothing without a split, and on a split they sum to ``step_xla_ms``."""
import json

import pytest

from bench import harness, scopes, spec, trace


def _read(name, ctx):
    return harness._reader(spec.HERE / "metrics", name)(ctx)


def _split(seconds: dict) -> scopes.ScopeTimes:
    return scopes.ScopeTimes(dict(dict.fromkeys(scopes.LABELS, 0.0),
                                  **seconds), 0.0,
                             dict.fromkeys(scopes.RULES, 0.0))


@pytest.mark.parametrize("name", list(scopes.METRICS))
def test_reader_reads_nothing_without_a_split(name):
    assert _read(name, {"steps": 3}) is None
    # a program that names no layer: all of the step is ``other``
    assert _read(name, {"steps": 3,
                        "scopes": _split({scopes.OTHER: 0.9})}) is None


def test_readers_sum_to_step_xla_ms():
    seconds = {lb: 1e-3 * (i + 1) for i, lb in enumerate(scopes.LABELS)}
    ctx = {"steps": 3, "scopes": _split(seconds),
           "summary": trace.Summary(busy=[1.0], by_class=[
               {"step": sum(seconds.values())}])}
    got = {name: _read(name, ctx) for name in scopes.METRICS}
    assert got["bucket_ms"] == pytest.approx(
        1e3 * (seconds["bucket.pack"] + seconds["bucket.unpack"]) / 3)
    assert got["model_bwd_ms"] == pytest.approx(
        1e3 * seconds["model.bwd"] / 3)
    assert sum(got.values()) == pytest.approx(_read("step_xla_ms", ctx),
                                              rel=1e-12)


def test_benchmark_lists_the_split():
    with open(spec.ROOT / "BENCHMARK.json") as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in scopes.METRICS:
        m = per_layer[name]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "ms/step", "lower", "device_trace", "tokens_per_s")
