"""Reduction of a profiler trace to the device times the per-layer
metrics read.

Each device-op event of the traced window is put in one of four
classes:

``mosaic``      a Mosaic kernel (a TPU custom call; in this program the
                fused error-feedback passes);
``collective``  all-gather, all-reduce, collective-permute,
                reduce-scatter, all-to-all (with their start/done
                halves);
``step``        any other operation of the training-step program;
``other``       an operation of any other program (the feed).

A device's busy time is the union of its op intervals; classes sum
each op's self time (its duration less that of the ops nested in it, as
a while loop's body ops are in the loop's event) per device.  Both are averaged over the cell's devices.  The
host spans that the harness writes (``bench.input``, ``bench.dispatch``,
``bench.wait``) label the longest gaps in which no op ran.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import re
from dataclasses import dataclass, field

STEP_PROGRAM = "step_fn"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|collective-permute|reduce-scatter|all-to-all)"
    r"(-start|-done)?(\.\d+)?$")
HOST_SPANS = ("bench.input", "bench.dispatch", "bench.wait")
CLASSES = ("mosaic", "collective", "step", "other")


@dataclass
class Op:
    name: str                        # HLO instruction name, e.g. fusion.3
    start_ns: float
    dur_ns: float
    program: str = ""
    opcode: str = ""                 # e.g. fusion, custom-call, all-gather
    mosaic: bool = False


# The profiler names a TPU op by its HLO instruction text:
# "%name = <result type> opcode(operands), attributes".
_HLO_TEXT = re.compile(
    r"^%?(?P<name>[^\s=]+) = .*?\s(?P<op>[a-z][a-z0-9-]*)\(")


@functools.lru_cache(maxsize=1 << 16)
def _parse(text: str) -> tuple:
    """(name, opcode, mosaic) of an op's text, parsed once per distinct
    text: a scan's body repeats the same ops once per iteration."""
    m = _HLO_TEXT.match(text)
    if not m:
        return text, "", False
    return (m.group("name"), m.group("op"),
            m.group("op") == "custom-call" and "tpu_custom_call" in text)


def op_from_event(text: str, start_ns, dur_ns, program: str) -> Op:
    name, opcode, mosaic = _parse(text)
    return Op(name, start_ns, dur_ns, program, opcode, mosaic)


@dataclass
class Summary:
    busy: list                       # per device, seconds
    by_class: list                   # per device, {class: seconds}
    top_ops: list = field(default_factory=list)
    gaps: list = field(default_factory=list)

    @property
    def busy_s(self) -> float:
        return sum(self.busy) / len(self.busy)

    def seconds(self, cls: str) -> float:
        return sum(d.get(cls, 0.0) for d in self.by_class) / len(
            self.by_class)

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops[:10], "idle_gaps": self.gaps[:10]}


def classify(op: Op) -> str:
    if op.mosaic:
        return "mosaic"
    if COLLECTIVE.match(op.opcode or op.name):
        return "collective"
    return "step" if STEP_PROGRAM in op.program else "other"


def self_times(ops) -> list:
    """Each op's duration less that of the ops nested in it: a while
    loop's event spans the ops of its body, which the trace lists too."""
    order = sorted(range(len(ops)),
                   key=lambda i: (ops[i].start_ns, -ops[i].dur_ns))
    own = [o.dur_ns for o in ops]
    stack = []                                  # indices of open ops
    for i in order:
        start, end = ops[i].start_ns, ops[i].start_ns + ops[i].dur_ns
        while stack and ops[stack[-1]].start_ns + ops[stack[-1]].dur_ns \
                < end:
            stack.pop()
        if stack:
            own[stack[-1]] -= ops[i].dur_ns
        stack.append(i)
    return [max(t, 0.0) for t in own]


def union_ns(intervals) -> tuple:
    """(total covered, merged intervals) of (start, end) pairs."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def summarize(devices_ops: list, host_spans: list, n_top: int = 10,
              ) -> Summary:
    """``devices_ops``: one list of ``Op`` per device.  ``host_spans``:
    (name, start_ns, end_ns) of the harness's host spans, on the same
    clock as the device ops."""
    busy, by_class, totals = [], [], {}
    for ops in devices_ops:
        covered, _ = union_ns((o.start_ns, o.start_ns + o.dur_ns)
                              for o in ops)
        busy.append(covered * 1e-9)
        cls = {c: 0.0 for c in CLASSES}
        for o, self_ns in zip(ops, self_times(ops)):
            c = classify(o)
            cls[c] += self_ns * 1e-9
            key = f"{c}:{o.name}"
            totals[key] = totals.get(key, 0.0) + self_ns * 1e-9 / len(
                devices_ops)
        by_class.append(cls)
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:n_top]
    gaps = []
    if devices_ops and devices_ops[0]:
        _, merged = union_ns((o.start_ns, o.start_ns + o.dur_ns)
                             for o in devices_ops[0])
        spans = sorted(host_spans, key=lambda s: s[1])
        starts = [s[1] for s in spans]
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            gaps.append([_host_label(spans, starts, (e0 + s1) / 2),
                         (s1 - e0) * 1e-9])
        gaps.sort(key=lambda g: -g[1])
    return Summary(busy=busy, by_class=by_class,
                   top_ops=[[k, v] for k, v in top], gaps=gaps[:n_top])


def _host_label(spans, starts, t) -> str:
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        name, s, e = spans[i]
        if s <= t <= e:
            return name
        i -= 1
        if i >= 0 and t - spans[i][2] > 1e9:
            break
    return "host: outside the bench spans"


# ---------------------------------------------------------------------------
# reading the profiler's XSpace
# ---------------------------------------------------------------------------


def find_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {directory},"
                                f" found {len(found)}")
    return found[0]


_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def load(source):
    from jax.profiler import ProfileData

    if isinstance(source, (str, os.PathLike)):
        return ProfileData.from_file(str(source))
    return source


def device_ops(pd, device_ids) -> list:
    """One ``Op`` list per device in ``device_ids``: the events of each
    device plane's "XLA Ops" line, each with the program ("XLA Modules"
    line) whose execution contains it."""
    planes = {}
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            planes[int(m.group(1))] = plane
    out = []
    for dev in device_ids:
        plane = planes[dev]
        lines = {line.name: list(line.events) for line in plane.lines}
        modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                         for ev in lines.get("XLA Modules", []))
        mstart = [m[0] for m in modules]
        ops = []
        for ev in lines.get("XLA Ops", []):
            i = bisect.bisect_right(mstart, ev.start_ns) - 1
            prog = modules[i][2] if i >= 0 and ev.start_ns <= modules[i][1] \
                else ""
            ops.append(op_from_event(ev.name, ev.start_ns, ev.duration_ns,
                                     prog))
        out.append(ops)
    return out


def host_spans(pd) -> list:
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in HOST_SPANS:
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return spans


def reduce(source, device_ids) -> Summary:
    pd = load(source)
    return summarize(device_ops(pd, device_ids), host_spans(pd))
