"""One run of one cell: set-up, the measured window, the check.

Set-up builds the step and its state once (``system.System``), drives
that same state through the first ``check_steps`` steps with the
window's own call and feed, and keeps what the comparison reads from
them.  Those steps also compile every program the window uses.  The
window then goes on from the same state: steps are dispatched with at
most ``DEPTH`` in flight and no host sync of the step just dispatched,
and it ends on the completion of its last step.  Once it has closed and
the peak memory is read, the program's state is freed and the reference
runs the same first steps (``reference.first_steps``) with the
model of the cell's family.  A traced run also splits the step's
device time by the layers the program names (``bench/scopes.py``).
"""
from __future__ import annotations

import collections
import gc
import tempfile
import time

import jax
import numpy as np

from bench import compare, flops, reference, scopes, spec, system, trace
from bench.peaks import peaks_of

DEPTH = 2            # steps in flight in the window
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class _CompileWatch:
    """Counts tracing, compiling and cache loads while ``on``."""

    def __init__(self):
        self.on, self.count, self.names = False, 0, []
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **kw):
        if self.on and name in COMPILE_EVENTS:
            self.count += 1
            self.names.append(kw.get("fun_name", name))


_WATCH = None


def _watch():
    global _WATCH
    if _WATCH is None:
        _WATCH = _CompileWatch()
    return _WATCH


def _ann(name):
    return jax.profiler.TraceAnnotation(name)


def run(cell: dict, seed: int, seconds: float, traced: bool, *, t0: float,
        fault: str | None = None) -> dict:
    """Run ``cell`` once; returns the result line's fields (without the
    device's platform check, which ``run.py`` makes first)."""
    model, job, family = cell["model"], cell["job"], cell["family"]
    watch = _watch()
    n_check = job["check_steps"]

    # ---- set-up ----------------------------------------------------------
    if fault not in (None, CONTROL) + PROGRAM_FAULTS:
        raise ValueError(f"no such fault {fault!r}")
    sut = system.System(model, job, seed,
                        fault=None if fault == CONTROL else fault)
    devices = sut.devices
    state, prog, counters = first_steps(sut, n_check)
    setup_s = time.perf_counter() - t0

    # ---- the window ------------------------------------------------------
    tmp = tempfile.TemporaryDirectory(prefix="bench-trace-") if traced \
        else None
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tmp.name, profiler_options=opts)
    watch.on = True
    inflight, window_losses = collections.deque(), []
    i = n_check
    t_start = time.perf_counter()
    while True:
        with _ann("bench.input"):
            batch = sut.batch(i)
        with _ann("bench.dispatch"):
            state, m = sut.step(state, batch)
        i += 1
        inflight.append(m["loss"])
        window_losses.append(m["loss"])
        if len(inflight) > DEPTH:
            with _ann("bench.wait"):
                inflight.popleft().block_until_ready()
        if traced:
            if i - n_check >= job["trace_steps"]:
                break
        elif time.perf_counter() - t_start >= seconds:
            break
    with _ann("bench.wait"):
        jax.block_until_ready((state, list(inflight)))
    t_end = time.perf_counter()
    watch.on = False
    if traced:
        jax.profiler.stop_trace()
    steps = i - n_check
    window_s = t_end - t_start
    if watch.count:
        raise RuntimeError(f"{watch.count} programs were traced or compiled "
                           f"inside the measured window: {watch.names[:8]}")
    losses_w = np.asarray([float(x) for x in window_losses])
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    kind = devices[0].device_kind
    platform = devices[0].platform
    if traced:
        # the compiled text of the step the window ran, whose instruction
        # names the trace's ops carry
        hlo = sut.step.lower(state, batch).compile().as_text()

    # ---- free the program, then the reference ----------------------------
    del state, m, batch, inflight, window_losses
    mesh_devices = len(devices)
    tokens_per_step = sut.tokens_per_step
    del sut
    gc.collect()
    ref = reference.first_steps(family, model, job, seed, n_steps=n_check)
    if fault == CONTROL:
        prog = control(cell, seed)
    read = compare.readings(prog, ref)
    correct, checks = compare.verdict(read, cell["limits"])
    leaves = compare.leaf_lines(prog, ref,
                                reference.leaf_shapes(family, model)[0])

    out = {
        "correct": correct,
        "attempted": steps,
        "failed": int(np.sum(~np.isfinite(losses_w))),
        "device": {"platform": platform, "kind": kind, "count": mesh_devices,
                   "memory_peak_bytes": int(peak)},
        "checks": checks,
        "leaves": leaves,
        "counters": counters,
    }
    if not traced:
        values = {"tokens_per_s": steps * tokens_per_step / window_s,
                  "setup_s": setup_s}
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell["end_to_end"]}
        return out

    # ---- per-layer metrics from the trace --------------------------------
    ids = [d.id for d in devices]
    profile = trace.load(trace.find_xplane(tmp.name))
    summary = trace.reduce(profile, ids)
    split = scopes.reduce(profile, ids, hlo)
    del profile
    tmp.cleanup()
    ctx = {
        "summary": summary, "scopes": split, "counters": counters,
        "steps": steps, "window_s": window_s,
        "chips": mesh_devices, "peaks": peaks_of(kind),
        "train_flops_per_step": flops.train_flops_per_token(
            family, model, job["seq"]) * tokens_per_step,
        "model": model, "job": job,
    }
    metrics = {}
    for m in cell["per_layer"]:
        value = _reader(cell["metrics_dir"], m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"]["busy_s"] = summary.busy_s
    out["device"]["window_s"] = window_s
    out["breakdown"] = summary.breakdown()
    return out


CONTROL = "control"
PROGRAM_FAULTS = ("unchanged", "half_batch", "answer", "fused")


def control(cell, seed):
    """The control: the reference's first steps in bfloat16, put in the
    program's place."""
    return reference.first_steps(cell["family"], cell["model"], cell["job"],
                                 seed, n_steps=cell["job"]["check_steps"],
                                 dtype=jax.numpy.bfloat16)


def first_steps(sut, n_check):
    """Make the state and drive it through the first ``n_check`` steps
    with the window's own call and feed.  Returns the state and what the
    comparison reads: each step's loss, the momentum's leaf norms and
    nonzero counts after one step (the first aggregated gradient), the
    leaf norms of the parameters' change after one step and after
    ``n_check`` steps; and the step's counters."""
    state = sut.new_state()
    losses, update_norms, update_counts, counters = [], None, None, {}
    for i in range(n_check):
        state, m = sut.step(state, sut.batch(i))
        losses.append(m["loss"])
        if i == 0:
            update_norms = system.leaf_norms(state["opt"]["m"])
            update_counts = system.leaf_counts(state["opt"]["m"])
            first_change = _change_norms(sut, state)
            counters = {k: m[k] for k in ("comm_bits_sparse",
                                          "comm_bits_dense",
                                          "collectives_per_step",
                                          "density", "ef_leaves_at_cap",
                                          "ef_leaves_under_band") if k in m}
    prog = {"loss": [float(x) for x in losses],
            "update_norms": np.asarray(update_norms),
            "update_counts": np.asarray(update_counts),
            "first_change_norms": first_change,
            "change_norms": _change_norms(sut, state)}
    jax.block_until_ready(state)
    return state, prog, {k: float(v) for k, v in counters.items()}


def _change_norms(sut, state):
    """Leaf norms of the parameters' change from the initial ones, which
    are made anew for it and freed before the next step."""
    p0 = sut.initial_params()
    change = np.asarray(system.change_norms(state["params"], p0))
    del p0
    return change


def _reader(directory, name):
    """The reader of metric ``name``: ``metrics/<name>.py``."""
    return spec.module(directory / f"{name}.py", "bench_metric_").read
