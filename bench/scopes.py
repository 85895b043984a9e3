#!/usr/bin/env python3
"""Device time of the training step by layer, read from the names the
program gives its layers (``jax.named_scope``, DESIGN.md §16).

XLA keeps each scope in the ``op_name`` metadata of the compiled step's
instructions.  An instruction belongs to the innermost scope of
``SCOPES`` that its ``op_name`` holds; ``model`` splits into its forward
(``model.fwd``) and its backward (``model.bwd``: names under
``transpose(...)``, remat's recompute with them).  A fusion takes the
scope of its own ``op_name``; one whose fused instructions carry more
than one scope is *mixed*, and its time is also reported on its own.
An instruction the compiler left without an ``op_name`` takes the scope
of its neighbours (``hlo_scopes`` gives the rules and their order); the
time each rule labeled is reported beside the split
(``scopes_by_<rule>_ms``), so that a change of the step's shape that
moves a number by attribution alone shows.
Joined to the trace's ops by HLO instruction name (``trace.Op.name``),
the self times of the step program's ops (class ``step`` of
``bench/trace.py``) sum per scope; what no scope holds is ``other``.
The labels partition that class, so ``METRICS`` sum to ``step_xla_ms``.

    python3 bench/scopes.py DIR --steps N

reduces a trace of N steps written by ``repro.launch.train
--profile-dir DIR`` (its ``.xplane.pb`` and the compiled step's text,
``step.hlo.txt``) and prints one JSON object of ms per step; it refuses
a trace without a TPU plane.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import NamedTuple

if __package__ in (None, ""):                      # run as a script
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import trace  # noqa: E402

SCOPES = ("model", "bucket.pack", "bucket.unpack", "ef.select",
          "ef.compact", "ef.residual", "wire", "optimizer")
OTHER = "other"
LABELS = ("model.fwd", "model.bwd") + SCOPES[1:] + (OTHER,)
# per-layer metric -> the labels whose time it sums
METRICS = {
    "model_fwd_ms": ("model.fwd",),
    "model_bwd_ms": ("model.bwd",),
    "ef_select_ms": ("ef.select",),
    "ef_compact_ms": ("ef.compact",),
    "ef_residual_ms": ("ef.residual",),
    "bucket_ms": ("bucket.pack", "bucket.unpack"),
    "wire_ms": ("wire",),
    "optimizer_ms": ("optimizer",),
    "other_step_ms": (OTHER,),
}

# "transpose(jvp(model))" -> "model"; "ef.select" -> "ef.select"
_CALLEE = re.compile(r"^(?:[\w\-]+\()*(?P<name>[\w.\-]+)\)*$")
_INSTR = re.compile(
    r"^\s+(?:ROOT\s+)?%?(?P<name>[^\s=]+) = .*?\s(?P<op>[a-z][a-z0-9-]*)\(")
_CALLS = re.compile(r"\bcalls=%?(?P<comp>[\w.\-]+)")
_OP_NAME = re.compile(r'\bop_name="(?P<op_name>[^"]*)"')
_REF = re.compile(r"%(?P<ref>[\w.\-]+)")
_PARAM = re.compile(r"parameter\((?P<n>\d+)\)")
_REMAT = re.compile(r"^(?P<base>.+?)\.remat\d*$")
# ops that move a value without computing: a scatter's indices are
# traced back through them
_MOVES = ("bitcast", "reshape", "transpose", "copy", "convert")
# how an instruction got its label, in the order ``hlo_scopes`` tries
RULES = ("op_name", "root", "scatter_index", "users", "operands", "fused",
         "remat")


def scope_of(op_name: str) -> str | None:
    """The label of an instruction's ``op_name``: its innermost scope,
    ``model`` as ``model.fwd`` or ``model.bwd``; None outside them."""
    label, transposed = None, False
    for part in op_name.split("/"):
        transposed = transposed or "transpose(" in part
        m = _CALLEE.match(part)
        name = m.group("name") if m else part
        if name == "model":
            label = "model.bwd" if transposed else "model.fwd"
        elif name in SCOPES:
            label = name
    return label


class _Instr(NamedTuple):
    name: str
    op: str                  # opcode
    own: str | None          # label of its own op_name, OTHER outside all
    calls: str | None        # a fusion's fused computation
    operands: tuple
    param: int | None        # a parameter's number


def _operands(line: str, start: int) -> tuple:
    """The instruction names inside the operand list that opens just
    before ``start`` (operand types may hold parentheses)."""
    depth, end = 1, start
    while depth and end < len(line):
        depth += {"(": 1, ")": -1}.get(line[end], 0)
        end += 1
    return tuple(_REF.findall(line[start:end]))


def _parse(hlo_text: str) -> dict:
    """Computation name -> (its instructions in the text's order, the
    name of its root).  A scheduled module lists them in execution
    order, operands before users."""
    comps, roots, comp = {}, {}, None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            comp = None
            if line.rstrip().endswith("{"):
                comp = (line.split()[1] if line.startswith("ENTRY ")
                        else line.split()[0]).lstrip("%")
                comps[comp] = []
            continue
        m = _INSTR.match(line)
        if not m or comp is None:
            continue
        op_name = _OP_NAME.search(line)
        called = _CALLS.search(line) if m.group("op") == "fusion" else None
        param = (_PARAM.search(line, m.start("op"))
                 if m.group("op") == "parameter" else None)
        comps[comp].append(_Instr(
            m.group("name"), m.group("op"),
            (scope_of(op_name.group("op_name")) or OTHER) if op_name
            else None,
            called.group("comp") if called else None,
            _operands(line, m.end()),
            int(param.group("n")) if param else None))
        if line.lstrip().startswith("ROOT "):
            roots[comp] = m.group("name")
    return {c: (instrs, roots.get(c)) for c, instrs in comps.items()}


class HloScopes(NamedTuple):
    labels: dict     # instruction name -> label, for those in a scope
    mixed: set       # fusions whose fused instructions hold two scopes
    rules: dict      # instruction name -> the rule of RULES that labeled it


def hlo_scopes(hlo_text: str) -> HloScopes:
    """The label of every instruction of a compiled module in a scope,
    by instruction name; the fusions whose fused instructions carry
    more than one scope; and the rule that gave each label.

    The compiler leaves many instructions without an ``op_name``: its
    own copies and layout changes, and ops it rewrote (a batched
    scatter it rewrites loses its ``op_name``).  Such an instruction
    takes, by the first of ``RULES`` that gives one: its fused root's
    label (through nested fusions); for a scatter at its root, the label
    of the op that made its indices (traced back through moves and
    fusion parameters, ``_MOVES``, to the one label of its nearest
    labeled producers), since the indices say what the scatter does —
    a compaction's slots or a decode's coordinates; the one label its
    users agree on; the one label its operands agree on; the one scope
    of its fused instructions.  A clone the compiler made to
    rematerialize a value takes its original's label.  An ``op_name``
    outside every scope is ``other`` and votes as such."""
    comps = _parse(hlo_text)
    by_name = {i.name: i for instrs, _ in comps.values() for i in instrs}

    def root_of(comp):
        return by_name.get(comps.get(comp, ((), None))[1])

    def fused(comp, depth=0) -> set:
        """The scopes of the instructions of a fused computation."""
        out = set()
        for ins in comps.get(comp, ((), None))[0]:
            if ins.own and ins.own != OTHER:
                out.add(ins.own)
            if ins.calls and depth < 8:
                out |= fused(ins.calls, depth + 1)
        return out

    def root_label(comp, depth=0):
        ins = root_of(comp)
        if ins is None or ins.own or not ins.calls or depth >= 8:
            return ins.own if ins else None
        return root_label(ins.calls, depth + 1)

    def named(ins):
        return ins.own if ins.own != OTHER else None

    def trace_back(name, labeled):
        """Follow ``name`` back through moves to a label, or to the
        number of the fused computation's parameter it comes from."""
        for _ in range(64):
            ins = by_name.get(name)
            if ins is None:
                return None, None
            got = labeled(ins)
            if got:
                return got, None
            if ins.param is not None:
                return None, ins.param
            if ins.op not in _MOVES or not ins.operands:
                return None, None
            name = ins.operands[0]
        return None, None

    def scatter_indices(ins, labeled, depth=0):
        """``(label, parameter)`` of the indices of the unnamed scatter
        at ``ins`` or at the root of its fusions."""
        if ins is None or ins.own or depth >= 8:
            return None, None
        if ins.op == "scatter" and ins.operands:
            return trace_back(ins.operands[(len(ins.operands) - 1) // 2],
                              labeled)
        if not ins.calls:
            return None, None
        got, p = scatter_indices(root_of(ins.calls), named, depth + 1)
        if got or p is None or p >= len(ins.operands):
            return got, None
        return trace_back(ins.operands[p], labeled)

    labels, rules, mixed = {}, {}, set()
    for instrs, _ in comps.values():
        lab = {i.name: i.own for i in instrs if i.own}
        how = dict.fromkeys(lab, "op_name")

        def give(name, label, rule):
            if label:
                lab[name], how[name] = label, rule

        users: dict = {}
        for ins in instrs:
            for ref in ins.operands:
                if ref != ins.name:
                    users.setdefault(ref, []).append(ins.name)
            if ins.calls:
                if len(fused(ins.calls)) > 1:
                    mixed.add(ins.name)
                if ins.name not in lab:
                    give(ins.name, root_label(ins.calls), "root")

        def agreed(names):
            found = {lab[n] for n in names if n in lab}
            return found.pop() if len(found) == 1 else None

        def upstream(ins):
            """The one label the nearest labeled producers of ``ins``
            agree on, through unlabeled instructions of its
            computation."""
            seen, todo, found = set(), [ins.name], set()
            while todo and len(seen) < 256:
                name = todo.pop()
                if name in seen:
                    continue
                seen.add(name)
                if name in lab:
                    found.add(lab[name])
                elif name in by_name:
                    todo.extend(by_name[name].operands)
            return found.pop() if len(found) == 1 else None

        for ins in instrs:
            if ins.name not in lab:
                give(ins.name, scatter_indices(ins, upstream)[0],
                     "scatter_index")
        for ins in reversed(instrs):
            if ins.name not in lab:
                give(ins.name, agreed(users.get(ins.name, ())), "users")
        for ins in instrs:
            if ins.name in lab:
                continue
            give(ins.name, agreed(ins.operands), "operands")
            if ins.name not in lab and ins.calls and len(
                    fused(ins.calls)) == 1:
                give(ins.name, next(iter(fused(ins.calls))), "fused")
        labels.update(lab)
        rules.update(how)
    for name, ins in by_name.items():
        m = _REMAT.match(name)
        if m and not ins.own and m.group("base") in labels:
            labels[name] = labels[m.group("base")]
            rules[name] = "remat"
    keep = {n: lb for n, lb in labels.items() if lb != OTHER}
    return HloScopes(keep, mixed, {n: rules[n] for n in keep})


@dataclass
class ScopeTimes:
    seconds: dict          # label -> self seconds, averaged over devices
    mixed_s: float         # self seconds of mixed fusions, the same way
    rule_s: dict           # rule of RULES -> self seconds it labeled

    def metrics_ms(self, steps: int) -> dict:
        """``METRICS`` in ms per step; empty where the program names no
        scope (a program that predates them)."""
        if not any(self.seconds[lb] for lb in LABELS if lb != OTHER):
            return {}
        return {name: 1e3 * sum(self.seconds[lb] for lb in labels) / steps
                for name, labels in METRICS.items()}

    def rules_ms(self, steps: int) -> dict:
        """The time each rule of ``hlo_scopes`` put in a scope, in ms
        per step: ``scopes_by_op_name_ms`` is what the program named
        itself, the others what was inferred from neighbours."""
        return {f"scopes_by_{rule}_ms": 1e3 * self.rule_s[rule] / steps
                for rule in RULES}


def attribute(devices_ops: list, scopes: HloScopes) -> ScopeTimes:
    """Sum the self times of each device's class-``step`` ops by label."""
    seconds = dict.fromkeys(LABELS, 0.0)
    rule_s = dict.fromkeys(RULES, 0.0)
    mixed_s = 0.0
    n = len(devices_ops)
    for ops in devices_ops:
        for op, own_ns in zip(ops, trace.self_times(ops)):
            if trace.classify(op) != "step":
                continue
            t = own_ns * 1e-9 / n
            seconds[scopes.labels.get(op.name, OTHER)] += t
            if op.name in scopes.rules:
                rule_s[scopes.rules[op.name]] += t
            if op.name in scopes.mixed:
                mixed_s += t
    return ScopeTimes(seconds, mixed_s, rule_s)


def reduce(source, device_ids, hlo_text: str) -> ScopeTimes:
    """``source``: an ``.xplane.pb`` path or a loaded ``ProfileData``;
    ``hlo_text``: the compiled step's ``as_text()``."""
    return attribute(trace.device_ops(trace.load(source), device_ids),
                     hlo_scopes(hlo_text))


def _tpu_ids(pd) -> list:
    return sorted(int(p.name.rsplit(":", 1)[1]) for p in pd.planes
                  if p.name.startswith("/device:TPU:"))


def main(argv=None) -> int:
    import argparse
    import json
    import os

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("dir", help="a --profile-dir of repro.launch.train")
    ap.add_argument("--steps", type=int, required=True,
                    help="steps the trace holds (b - a of --profile-steps)")
    args = ap.parse_args(argv)
    pd = trace.load(trace.find_xplane(args.dir))
    ids = _tpu_ids(pd)
    if not ids:
        raise SystemExit(f"{args.dir}: the trace holds no /device:TPU "
                         "plane, so it has no device time to split")
    with open(os.path.join(args.dir, "step.hlo.txt")) as f:
        times = reduce(pd, ids, f.read())
    out = times.metrics_ms(args.steps)
    out["scopes_mixed_ms"] = 1e3 * times.mixed_s / args.steps
    out.update(times.rules_ms(args.steps))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
