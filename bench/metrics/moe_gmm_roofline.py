"""moe_gmm_roofline: the expert layers' grouped products' share of their
roofline, in %: ``max(F / bf16 peak, B / HBM peak)`` over their device
time a step (``moe_gmm_ms``, and None where it is), with the peaks of the chips'
``device_kind`` (``bench/peaks.py``).

``F`` and ``B`` are the work a step requires, recomputation not
counted, so the number reads the same work whatever implements it.
Each expert layer routes ``R = tokens * K * held / E`` rows to the
experts held here (the expected share; ``tokens`` a chip's per step).
Each of its three expert matrices (``held`` stacked ``din x dout``, P
elements in all) takes three products of ``2 R din dout`` FLOPs each:
the forward, the inputs' gradient and the weights' gradient.  Bytes:
the forward reads the weights in bf16 (2P) and its rows (2 R din) and
writes float32 rows (4 R dout); the inputs' gradient reads the weights
(2P) and 2 R dout, writes 4 R din; the weights' gradient reads both
row operands (2 R din + 2 R dout) and writes the float32 gradient (4P).
"""
from pathlib import Path

from bench import spec

_MS = spec.module(Path(__file__).with_name("moe_gmm_ms.py"), "bench_metric_")


def _moe_layers(model: dict) -> int:
    pattern = model["ffn_pattern"]
    n = model["num_layers"] - model.get("first_dense_layers", 0)
    return sum(pattern[i % len(pattern)] == "moe" for i in range(n))


def routed_rows(model: dict, tokens: int) -> float:
    held = model.get("experts_held") or model["num_experts"]
    return tokens * model["experts_per_token"] * held / model["num_experts"]


def _matrices(model: dict):
    """(elements, din, dout) of each expert matrix of one layer."""
    held = model.get("experts_held") or model["num_experts"]
    D, F = model["d_model"], model["moe_d_ff"]
    return [(held * D * F, D, F), (held * D * F, D, F),
            (held * F * D, F, D)]


def flops(model: dict, tokens: int) -> float:
    R = routed_rows(model, tokens)
    return _moe_layers(model) * sum(3 * 2.0 * R * din * dout
                                    for _, din, dout in _matrices(model))


def hbm_bytes(model: dict, tokens: int) -> float:
    R = routed_rows(model, tokens)
    one = sum((2 * P + 2 * R * din + 4 * R * dout)
              + (2 * P + 2 * R * dout + 4 * R * din)
              + (2 * R * din + 2 * R * dout + 4 * P)
              for P, din, dout in _matrices(model))
    return _moe_layers(model) * one


def read(ctx):
    t = _MS.seconds_per_step(ctx)
    if t is None:
        return None
    job, peaks = ctx["job"], ctx["peaks"]
    tokens = job["batch_per_worker"] * job["seq"] * job["workers"] \
        / ctx["chips"]
    need = max(flops(ctx["model"], tokens) / peaks["bf16_flops_per_s"],
               hbm_bytes(ctx["model"], tokens) / peaks["hbm_bytes_per_s"])
    return 100.0 * need / t
