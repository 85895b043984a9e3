"""The step's named layers (DESIGN.md §16) and the per-segment outcome
counters of the bucketed EF.

* the compiled step of the benchmark's CPU stand-in cell carries every
  scope, forward and backward model ops among them;
* ``bench/scopes.py`` splits a synthetic trace of the step program by
  the scopes of a synthetic HLO text, the parts summing to the step's
  class, a fusion of two scopes counted as mixed;
* ``ef_leaves_at_cap`` / ``ef_leaves_under_band`` equal a count taken
  from the wire block's own indices.
"""
import math
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import scopes, trace  # noqa: E402


# ---------------------------------------------------------------------------
# the scopes in the compiled step
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_step_labels():
    from bench import system
    from bench.tests import tiny

    cell = tiny.cell("stablelm_efjnp_1chip")
    sut = system.System(cell["model"], cell["job"], 2 ** 31 + 5)
    state = jax.eval_shape(sut.new_state)
    batch = jax.eval_shape(sut.batch, 0)
    text = sut.step.lower(state, batch).compile().as_text()
    return set(scopes.hlo_scopes(text).labels.values())


@pytest.mark.parametrize("label", [lb for lb in scopes.LABELS
                                   if lb != scopes.OTHER])
def test_step_hlo_carries_scope(tiny_step_labels, label):
    assert label in tiny_step_labels


@pytest.mark.parametrize("op_name, label", [
    ("jit(step_fn)/jit(main)/jvp(model)/dot_general", "model.fwd"),
    ("jit(step_fn)/transpose(jvp(model))/rematted_computation/mul",
     "model.bwd"),
    ("jit(step_fn)/ef.residual/ef.select/reduce_sum", "ef.select"),
    ("jit(step_fn)/ef.compact/jit(compact_residual)/pallas_call",
     "ef.compact"),
    ("jit(step_fn)/bucket.unpack/slice", "bucket.unpack"),
    ("jit(step_fn)/while/body/add", None),
])
def test_scope_of_is_the_innermost(op_name, label):
    assert scopes.scope_of(op_name) == label


# ---------------------------------------------------------------------------
# a synthetic trace reduced by a synthetic module's scopes
# ---------------------------------------------------------------------------

STEP = "jit(step_fn)/shard_map"
# (instruction, opcode, op_name below the step, start ms, duration ms,
#  fused computation): one device, the feed at 0-5 ms, the step program
#  at 10-95 ms
OPS = [
    ("fusion.2", "fusion", "jvp(model)/dot_general", 10, 10, "f2"),
    ("fusion.3", "fusion", "transpose(jvp(model))/dot_general", 20, 20,
     "f3"),
    ("while.4", "while", "ef.select/while", 40, 15, None),
    ("fusion.5", "fusion", "ef.select/reduce_sum", 42, 10, "f5"),
    ("fusion.6", "fusion", "ef.compact/cumsum", 55, 8, "f6"),
    ("compact_residual.7", "custom-call", "ef.compact/pallas_call", 63, 4,
     None),
    ("copy.8", "copy", "copy", 67, 2, None),
    ("all-gather.9", "all-gather", "wire/all_gather", 69, 3, None),
    ("fusion.10", "fusion", "optimizer/mul", 72, 5, "f10"),
    ("fusion.11", "fusion", "bucket.pack/concatenate", 77, 1, "f11"),
    ("fusion.12", "fusion", "bucket.unpack/slice", 78, 1, "f12"),
    ("fusion.13", "fusion", "wire/scatter-add", 79, 1, "f13"),
    ("fusion.14", "fusion", "ef.residual/sub", 80, 1, "f14"),
    # no op_name at all, as the compiler leaves a scatter it rewrote:
    # the scope is the reshape's of its result; and a clone of it made
    # to rematerialize the value
    ("fusion.15", "fusion", None, 81, 4, "f15"),
    ("fusion.15.remat2", "fusion", None, 85, 4, "f15"),
    # a compaction's scatter the compiler rewrote: no op_name, its
    # values from the residual's sum, its user a residual op; its
    # indices, the slots, come from the compaction's fusion.6
    ("fusion.16", "fusion", "ef.residual/add", 89, 1, "f16"),
    ("fusion.17", "fusion", None, 90, 3, "f17"),
]
# fused instructions beyond each fusion's root: f6 also holds a compare
# of the selection, so fusion.6 is mixed
FUSED_EXTRA = {"f6": "ef.select/gt"}
# entry instructions the trace does not time
ENTRY_EXTRA = ["  %reshape.18 = f32[8]{0} reshape(f32[8]{0} %fusion.15), "
               f'metadata={{op_name="{STEP}/ef.compact/scatter"}}',
               "  %reshape.19 = f32[8]{0} reshape(f32[8]{0} %fusion.17), "
               f'metadata={{op_name="{STEP}/ef.residual/sub"}}']
# operands of entry instructions other than the one parameter %p
ENTRY_OPERANDS = {"fusion.17": "f32[8]{0} %p, s32[8]{0} %fusion.6, "
                               "f32[8]{0} %fusion.16"}
# fused computations other than one root on one parameter
FUSED_TEXT = {"f17": """\
%f17 (p17.0: f32[8], p17.1: s32[8], p17.2: f32[8]) -> f32[8] {
  %p17.0 = f32[8]{0} parameter(0)
  %p17.1 = s32[8]{0} parameter(1)
  %bitcast.17 = s32[8]{0} bitcast(s32[8]{0} %p17.1)
  %p17.2 = f32[8]{0} parameter(2)
  ROOT %scatter.17 = f32[8]{0} scatter(f32[8]{0} %p17.0, s32[8]{0} \
%bitcast.17, f32[8]{0} %p17.2), to_apply=%assign
}
"""}
MS_PS = 1_000_000_000          # picoseconds per ms


def _event_text(name, opcode, calls):
    tail = (', custom_call_target="tpu_custom_call"'
            if opcode == "custom-call" else "")
    tail += f", calls=%{calls}" if calls else ""
    operands = ENTRY_OPERANDS.get(name, "f32[8]{0} %p")
    return f"%{name} = f32[8]{{0}} {opcode}({operands}){tail}"


def _xspace():
    events, meta = [], []
    feed = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    meta.append(f'event_metadata {{ key: 1 value {{ id: 1 '
                f'name: "{feed}" }} }}')
    events.append("events { metadata_id: 1 offset_ps: 0 "
                  f"duration_ps: {5 * MS_PS} }}")
    for i, (name, opcode, _, start, dur, calls) in enumerate(OPS, 2):
        text = _event_text(name, opcode, calls).replace('"', '\\"')
        meta.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{text}" }} }}')
        events.append(f"events {{ metadata_id: {i} offset_ps: "
                      f"{start * MS_PS} duration_ps: {dur * MS_PS} }}")
    n = len(OPS) + 2
    return f"""
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{
    id: 1
    name: "XLA Modules"
    timestamp_ns: 0
    events {{ metadata_id: {n} offset_ps: 0 duration_ps: {5 * MS_PS} }}
    events {{ metadata_id: {n + 1} offset_ps: {10 * MS_PS}
             duration_ps: {85 * MS_PS} }}
  }}
  lines {{
    id: 2
    name: "XLA Ops"
    timestamp_ns: 0
    {chr(10).join(events)}
  }}
  {chr(10).join(meta)}
  event_metadata {{ key: {n} value {{ id: {n} name: "jit__lambda(1)" }} }}
  event_metadata {{ key: {n + 1} value {{ id: {n + 1}
                   name: "jit_step_fn(2)" }} }}
}}
"""


def _hlo(named=True):
    def meta(path):
        if path is None:
            return ""
        return (f', metadata={{op_name="{STEP}/{path}"}}' if named
                else ', metadata={op_name="jit(step_fn)/add"}')

    lines = ["HloModule jit_step_fn, entry_computation_layout={()->()}", ""]
    for name, opcode, path, _, _, calls in OPS:
        if not calls or f"%{calls} " in "\n".join(lines):
            continue
        if calls in FUSED_TEXT:
            lines.append(FUSED_TEXT[calls])
            continue
        lines.append(f"%{calls} (param_0: f32[8]) -> f32[8] {{")
        lines.append("  %param_0 = f32[8]{0} parameter(0)")
        if calls in FUSED_EXTRA:
            lines.append(f"  %gt.{calls} = f32[8]{{0}} compare(f32[8]{{0}} "
                         f"%param_0){meta(FUSED_EXTRA[calls])}")
        lines.append(f"  ROOT %root.{calls} = f32[8]{{0}} add(f32[8]{{0}} "
                     f"%param_0){meta(path)}")
        lines += ["}", ""]
    lines.append("ENTRY %main.1 (Arg_0.1: f32[8]) -> f32[8] {")
    for name, opcode, path, _, _, calls in OPS:
        lines.append(f"  {_event_text(name, opcode, calls)}{meta(path)}")
    lines += ENTRY_EXTRA if named else []
    lines.append("  ROOT %tuple.1 = (f32[8]{0}) tuple(f32[8]{0} %copy.8)")
    lines.append("}")
    return "\n".join(lines)


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(_xspace())


def test_scope_seconds_partition_the_step(profile):
    times = scopes.reduce(profile, [0], _hlo())
    want = {"model.fwd": 10, "model.bwd": 20, "ef.select": 15,
            "ef.compact": 19, "ef.residual": 2, "bucket.pack": 1,
            "bucket.unpack": 1, "wire": 1, "optimizer": 5, "other": 2}
    assert times.seconds == pytest.approx(
        {k: v * 1e-3 for k, v in want.items()})
    step_s = trace.reduce(profile, [0]).seconds("step")
    assert sum(times.seconds.values()) == pytest.approx(step_s)
    # the Mosaic call and the all-gather are not of the step class
    assert step_s == pytest.approx(76e-3)
    # what each rule put in a scope: all but ``other``
    assert times.rules_ms(steps=1) == pytest.approx({
        "scopes_by_op_name_ms": 63, "scopes_by_root_ms": 0,
        "scopes_by_scatter_index_ms": 3, "scopes_by_users_ms": 4,
        "scopes_by_operands_ms": 0, "scopes_by_fused_ms": 0,
        "scopes_by_remat_ms": 4})
    assert times.mixed_s == pytest.approx(8e-3)      # fusion.6 alone
    ms = times.metrics_ms(steps=2)
    assert set(ms) == set(scopes.METRICS)
    assert sum(ms.values()) == pytest.approx(1e3 * step_s / 2)
    assert ms["bucket_ms"] == pytest.approx(1.0)
    assert ms["other_step_ms"] == pytest.approx(1.0)


def test_a_program_without_scopes_reads_nothing(profile):
    times = scopes.reduce(profile, [0], _hlo(named=False))
    assert times.seconds["other"] == pytest.approx(76e-3)
    assert times.metrics_ms(steps=2) == {}
    assert times.mixed_s == 0


# ---------------------------------------------------------------------------
# the per-segment outcome counters
# ---------------------------------------------------------------------------

MSIZE, RATIO, D = 2, 0.01, 4096


def _leaves(case):
    rng = np.random.default_rng(3)
    leaves = {"a": rng.standard_normal(D), "n": rng.standard_normal(D)}
    if case == "forced":
        # half of each row at one value: the refinement cannot reach its
        # band, the threshold ends under 1 and every row over-runs its
        # capacity; an all-zero leaf keeps nothing
        leaves["n"] = np.tile(np.repeat([1.0, 0.0], 256), D // 512)
        leaves["z"] = np.zeros(D // 4)
    return {k: jnp.asarray(v, jnp.float32) for k, v in leaves.items()}


def _count(indices, layout, banded=True):
    """The counters taken straight from a wire block's index slices."""
    at_cap = under = 0
    for s in layout.segments:
        kept = int((indices[:, s.cap_off:s.cap_off + s.k_cap] != -1).sum())
        at_cap += kept == MSIZE * s.k_cap
        under += banded and kept < MSIZE * math.ceil(2 * s.k_row / 3)
    return at_cap, under


@pytest.mark.parametrize("name, case, want", [
    ("gaussiank", "forced", (1, 1)), ("gaussiank", "none", (0, 0)),
    # hist-k's one-pass threshold has no band: under_band stays 0
    ("histk", "forced", (1, 0))])
def test_segment_outcome_counters(name, case, want):
    from repro.core import get_compressor
    from repro.core.compression import CompressionConfig
    from repro.dist import aggregate
    from repro.dist.layout import build_layout, pack_grads
    from repro.launch.mesh import make_mesh

    grads = _leaves(case)
    spec = get_compressor(name)
    layout = build_layout(grads, MSIZE, RATIO, spec)
    config = CompressionConfig(compressor=name, ratio=RATIO,
                               backend="reference")
    resid = jnp.zeros((MSIZE * layout.d_row_total,), jnp.float32)
    key = jax.random.PRNGKey(0)

    def agg(g, e):
        return aggregate.aggregate_bucketed(g, e, layout, config, ("data",),
                                            "model", key).metrics

    metrics = jax.jit(jax.shard_map(
        agg, mesh=make_mesh((1, 1), ("data", "model")), in_specs=(P(), P()),
        out_specs=P(), axis_names={"data"}, check_vma=False))(grads, resid)
    G = pack_grads(layout, grads, jnp.float32)
    _, indices, _, _ = aggregate.bucket_compress(
        G, resid.reshape(MSIZE, -1), layout, spec, key)
    counted = _count(np.asarray(indices), layout, spec.banded)
    assert counted == want
    assert (float(metrics["ef_leaves_at_cap"]),
            float(metrics["ef_leaves_under_band"])) == counted
