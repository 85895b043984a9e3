"""The trace reduction on a small synthetic trace: busy time as the
union of op intervals, idle share, and the attribution of ops to Mosaic
kernels, collectives, the step program and other programs."""
import pytest

from bench import trace

MS = 1_000_000   # ns

# one device, a 100 ms window: the feed (other program) at 0-5 ms, the
# step program at 10-90 ms: a while loop at 10-50 ms whose body holds a
# 30 ms matmul, Mosaic kernels at 50-55 and 60-70 ms, an all-gather at
# 80-90 ms, and gaps at 55-60 and 70-80 ms
XSPACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Modules"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000000 }
    events { metadata_id: 2 offset_ps: 10000000000 duration_ps: 80000000000 }
  }
  lines {
    id: 2
    name: "XLA Ops"
    timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 5000000000 }
    events { metadata_id: 7 offset_ps: 10000000000 duration_ps: 40000000000 }
    events { metadata_id: 4 offset_ps: 10000000000 duration_ps: 30000000000 }
    events { metadata_id: 5 offset_ps: 50000000000 duration_ps: 5000000000
 }
    events { metadata_id: 5 offset_ps: 60000000000 duration_ps: 10000000000
 }
    events { metadata_id: 6 offset_ps: 80000000000 duration_ps: 10000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "jit__lambda(7)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_step_fn(3)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.1 = s32[2,1024]{1,0:T(2,128)} fusion(u32[2]{0:T(128)} %p), kind=kLoop, calls=%fused_computation" } }
  event_metadata { key: 4 value { id: 4 name: "%convolution.2 = f32[2048,2048]{1,0:T(8,128)} convolution(f32[2048,2048]{1,0:T(8,128)} %a, f32[2048,2048]{1,0:T(8,128)} %b), dim_labels=bf_io->bf" } }
  event_metadata { key: 5 value { id: 5 name: "%compact_residual.9 = (f32[8,64]{1,0:T(8,128)}, s32[8,64]{1,0:T(8,128)}) custom-call(f32[1]{0:T(128)S(6)} %max.46, f32[64,128]{1,0:T(8,128)} %g), custom_call_target=\\\"tpu_custom_call\\\"" } }
  event_metadata { key: 7 value { id: 7 name: "%while.5 = (s32[]{:T(128)}, f32[8]{0}) while((s32[]{:T(128)}, f32[8]{0}) %t), condition=%c, body=%b" } }
  event_metadata { key: 6 value { id: 6 name: "%all-gather.4 = s32[4,1,512]{2,1,0:T(1,128)} all-gather(s32[1,1,512]{2,1,0:T(1,128)} %i), replica_groups={{0,1,2,3}}, dimensions={0}" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "python"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 5000000000 duration_ps: 6000000000 }
    events { metadata_id: 2 offset_ps: 71000000000 duration_ps: 9000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.input" } }
  event_metadata { key: 2 value { id: 2 name: "bench.wait" } }
}
"""


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData

    return trace.reduce(ProfileData.from_text_proto(XSPACE), [0])


def test_busy_is_the_union_of_op_intervals(summary):
    # 0-5, 10-55 (the loop, then a kernel), 60-70, 80-90 ms
    assert summary.busy_s == pytest.approx(70e-3)


def test_attribution(summary):
    assert summary.seconds("other") == pytest.approx(5e-3)
    # the loop's own 10 ms (40 less its 30 ms body) and the matmul
    assert summary.seconds("step") == pytest.approx(40e-3)
    assert summary.seconds("mosaic") == pytest.approx(15e-3)
    assert summary.seconds("collective") == pytest.approx(10e-3)


def test_idle_share_and_gaps(summary):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "idle", trace.__file__.replace("trace.py", "metrics/idle_share.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    share = mod.read({"summary": summary, "window_s": 100e-3})
    assert share == pytest.approx(30.0)
    gaps = summary.breakdown()["idle_gaps"]
    # longest first; each labelled by the host span over its midpoint
    assert [g[0] for g in gaps] == ["bench.wait", "bench.input",
                                    "host: outside the bench spans"]
    assert [g[1] for g in gaps] == pytest.approx([10e-3, 5e-3, 5e-3])


def test_top_ops(summary):
    top = dict(summary.breakdown()["device_ops"])
    assert top["step:convolution.2"] == pytest.approx(30e-3)
    assert top["step:while.5"] == pytest.approx(10e-3)
    assert top["mosaic:compact_residual.9"] == pytest.approx(15e-3)
    assert len(top) == 5


def test_collective_names():
    for opcode in ("all-gather", "all-reduce-start", "all-gather-done",
                   "collective-permute", "reduce-scatter", "all-to-all"):
        op = trace.op_from_event(
            f"%x.1 = f32[4]{{0}} {opcode}(f32[1]{{0}} %y), dimensions={{0}}",
            0, 1, "jit_step_fn(1)")
        assert trace.classify(op) == "collective", opcode
    fusion = "%fusion.3 = f32[4]{0} fusion(f32[4]{0} %y), kind=kLoop"
    assert trace.classify(trace.op_from_event(fusion, 0, 1,
                                              "jit_step_fn(1)")) == "step"
    assert trace.classify(trace.op_from_event(fusion, 0, 1,
                                              "jit__lambda(2)")) == "other"
