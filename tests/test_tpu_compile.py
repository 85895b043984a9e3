"""Ahead-of-time TPU compiles of the fused EF kernels at real width.

Every kernel of the main path, and the whole fused pipeline, is compiled
by Mosaic for one chip of a described ``v5e:2x2`` topology at d = 2^24 —
no chip needed, only the TPU compiler.  What the interpreter accepts but
the chip refuses (a block off the (8, 128) tiling, an op Mosaic cannot
lower, more VMEM than a kernel may use) fails here.  The compiled HLO
must hold the Mosaic kernels as ``tpu_custom_call``s.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import codec
from repro.kernels.ef_fused import fused_compress_ef
from repro.kernels.ef_fused.compact_residual import compact_residual
from repro.kernels.ef_fused.fused_moments import fused_moments
from repro.kernels.ef_fused.tree_count import tree_count

D = 2 ** 24
DTYPES = (jnp.float32, jnp.bfloat16)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without the chip: keep it out of the cache
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _kernels(fn, *args) -> int:
    """Compile ``fn`` for the described chip; count its Mosaic kernels."""
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


def _min_block(dtype) -> int:
    return 1024 if dtype == jnp.float32 else 2048


@pytest.mark.parametrize("with_e", [False, True], ids=["g", "g+e"])
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("with_hist", [False, True], ids=["moments", "hist"])
def test_fused_moments_compiles(one_chip, dtype, with_e, with_hist):
    block = 4 * _min_block(dtype)
    g = jax.ShapeDtypeStruct((D // block, block), dtype, sharding=one_chip)
    e = jax.ShapeDtypeStruct(g.shape, jnp.float32, sharding=one_chip)

    def f(g, e=None):
        return fused_moments(g, e, block=block, with_hist=with_hist,
                             backend="mosaic", interpret=False)

    assert _kernels(f, *((g, e) if with_e else (g,))) == 1


@pytest.mark.parametrize("with_e", [False, True], ids=["g", "g+e"])
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_tree_count_compiles(one_chip, dtype, with_e):
    block = 4 * _min_block(dtype)
    g = jax.ShapeDtypeStruct((D // block, block), dtype, sharding=one_chip)
    e = jax.ShapeDtypeStruct(g.shape, jnp.float32, sharding=one_chip)
    t = jax.ShapeDtypeStruct((15,), jnp.float32, sharding=one_chip)

    def f(g, t, e=None):
        return tree_count(g, e, t, n_t=15, block=block, backend="mosaic",
                          interpret=False)

    assert _kernels(f, *((g, t, e) if with_e else (g, t))) == 1


@pytest.mark.parametrize("with_e", [False, True], ids=["g", "g+e"])
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_compact_residual_compiles(one_chip, dtype, with_e):
    block = _min_block(dtype)
    g = jax.ShapeDtypeStruct((D // block, block), dtype, sharding=one_chip)
    e = jax.ShapeDtypeStruct(g.shape, jnp.float32, sharding=one_chip)
    t = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)

    def f(g, t, e=None):
        return compact_residual(g, e, t, bcap=64, k_cap=D // 750,
                                block=block, out_dtype="float32",
                                backend="mosaic", interpret=False)

    assert _kernels(f, *((g, t, e) if with_e else (g, t))) == 1


@pytest.mark.parametrize("with_e", [False, True], ids=["g", "g+e"])
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("name,passes", [("gaussiank", 3), ("histk", 2)])
def test_fused_pipeline_compiles(one_chip, name, passes, dtype, with_e):
    """The whole pipeline: one Mosaic kernel per HBM pass (DESIGN.md §8)
    and no operand copies around them."""
    g = jax.ShapeDtypeStruct((D,), dtype, sharding=one_chip)
    e = jax.ShapeDtypeStruct((D,), jnp.float32, sharding=one_chip)

    def f(g, e=None):
        return fused_compress_ef(g, e, name, D // 1000, backend="mosaic")

    args = (g, e) if with_e else (g,)
    compiled = jax.jit(f).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == passes
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("name,kernels", [
    ("gaussiank", ("compact_residual", "fused_moments", "tree_count")),
    ("histk", ("compact_residual", "fused_moments"))])
def test_fused_pipeline_kernels_are_named(one_chip, name, kernels):
    """Each Mosaic kernel's instruction takes its ``pallas_call`` name,
    by which a device trace names the op, and holds the scope of its
    pass (DESIGN.md §16): passes A and A' select, pass B compacts."""
    g = jax.ShapeDtypeStruct((D,), jnp.float32, sharding=one_chip)
    e = jax.ShapeDtypeStruct((D,), jnp.float32, sharding=one_chip)

    def f(g, e):
        return fused_compress_ef(g, e, name, D // 1000, backend="mosaic")

    text = jax.jit(f).lower(g, e).compile().as_text()
    calls = [re.match(r'\s*(?:ROOT\s+)?%?([\w-]+)\.?\d*\s=.*op_name="([^"]*)"',
                      line).groups()
             for line in text.splitlines() if "tpu_custom_call" in line]
    assert sorted(n for n, _ in calls) == list(kernels)
    for kernel, op_name in calls:
        scope = "ef.compact" if kernel == "compact_residual" else "ef.select"
        assert f"/{scope}/" in op_name, (kernel, op_name)


def _sizes(text: str) -> dict:
    """Instruction name -> the element counts of the arrays it yields."""
    out = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*?) [\w\-]+\(", line)
        if m:
            out[m.group(1)] = [
                math.prod(int(n) for n in dims.split(",") if n)
                for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(2))]
    return out


def test_jnp_compaction_at_lm_head_has_no_scatter(one_chip):
    """The jnp Gaussian-k's compaction of the benchmark's LM head
    (stablelm-2-1.6b: 100352 x 2048, k_cap = ceil(4k/3) at ratio 0.001)
    walks down row counts and gathers rows: no d-sized scatter, which the
    chip runs an element at a time, no d-sized prefix sum or loop, only
    gathers of 128-wide rows or from arrays of at most d / 128 entries,
    and less temp memory than the scatter form and than the binary
    search of the cumsum that it replaced (1.64 GB)."""
    d, k_cap = 205_520_896, 274_028
    u = jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one_chip)
    t = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)

    def compiled(compact):
        return jax.jit(lambda u, t: compact(u, jnp.abs(u) > t)).lower(
            u, t).compile()

    def by_scatter(u, mask):
        mask = mask.astype(jnp.int32)
        return codec._compact_by_scatter(u, mask, jnp.cumsum(mask) - 1,
                                         k_cap)

    new = compiled(lambda u, mask: codec.compact_by_mask(u, mask, k_cap))
    old = compiled(by_scatter)
    ops = re.compile(r"= \S+ (scatter|gather)\(")
    assert set(ops.findall(new.as_text())) == {"gather"}
    assert "scatter" in ops.findall(old.as_text())
    assert (new.memory_analysis().temp_size_in_bytes
            <= old.memory_analysis().temp_size_in_bytes)
    assert new.memory_analysis().temp_size_in_bytes < 1.0e9
    text = new.as_text()
    sizes = _sizes(text)
    gathers = 0
    for line in text.splitlines():
        m = re.search(r"= .*? (reduce-window|while|gather)\((.*?)\)", line)
        if not m:
            continue
        operands = re.findall(r"%([\w.\-]+)", m.group(2))
        if m.group(1) == "gather":
            gathers += 1
            slices = re.search(r"slice_sizes=\{([\d,]*)\}", line).group(1)
            assert (slices.split(",")[-1] == "128"
                    or max(sizes[operands[0]]) <= d // 128), line
        else:
            assert all(n != d for name in operands for n in sizes[name]), line
    assert gathers == codec.row_levels(d) + 1


# ---------------------------------------------------------------------------
# the layers of the benchmark's stand-in step, as the chip's compiler
# leaves them (DESIGN.md §16)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_step_v5e(one_chip):
    """The compiled text of the CPU stand-in of the benchmark's cell
    (``bench/tests/tiny.py``), compiled for the described chip, whose
    compiler drops the ``op_name`` of the scatters it rewrites; with
    the scopes ``bench/scopes.py`` reads from it."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from bench import scopes, system
    from bench.tests import tiny
    from repro.launch import mesh as launch_mesh

    device = next(iter(one_chip.device_set))

    def chip_mesh(shape, axes):
        assert tuple(shape) == (1, 1)
        return Mesh(np.array([device]).reshape(1, 1), tuple(axes),
                    axis_types=(AxisType.Auto,) * len(axes))

    cell = tiny.cell("stablelm_efjnp_1chip")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(launch_mesh, "make_mesh", chip_mesh)
        sut = system.System(cell["model"], cell["job"], 2 ** 31 + 5)
    state = jax.eval_shape(sut.new_state)
    rep = NamedSharding(sut.mesh, P())
    batch = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
        jax.eval_shape(sut.batch, 0))
    text = sut.step.lower(state, batch).compile().as_text()
    lines = {m.group("name"): line for line in text.splitlines()
             for m in [scopes._INSTR.match(line)] if m}
    return scopes._parse(text), scopes.hlo_scopes(text), lines


def _timed(comps):
    """The instructions a device trace times (those of computations no
    fusion calls: the entry, loop bodies), each with what it runs: the
    instructions of its fused computations, nested ones included."""
    called = {i.calls for instrs, _ in comps.values() for i in instrs
              if i.calls}

    def inner(comp, depth=0):
        for ins in comps[comp][0]:
            yield ins
            if ins.calls and depth < 8:
                yield from inner(ins.calls, depth + 1)

    for comp, (instrs, _) in comps.items():
        if comp not in called:
            for ins in instrs:
                yield ins, list(inner(ins.calls)) if ins.calls else [ins]


def _assigns(comps, line: str) -> bool:
    """Whether a scatter's combiner returns the update (``.at[].set``,
    the compaction's) rather than adding it (a decode's)."""
    region = re.search(r"to_apply=%?([\w.\-]+)", line).group(1)
    instrs, root = comps[region]
    by_name = {i.name: i for i in instrs}
    ins = by_name[root]
    while ins.op in ("bitcast", "copy") and ins.operands:
        ins = by_name[ins.operands[0]]
    return ins.op == "parameter"


@pytest.mark.parametrize("check", [
    "compaction_gathers", "compaction_row_counts", "decode_scatters",
    "model_not_ef"])
def test_tiny_step_layers_on_v5e(tiny_step_v5e, check):
    """Every row gather of ``codec.compact_by_mask`` (the stand-in's
    leaves all take the row form) is read as ``ef.compact``, and so is
    every one of its row reductions and prefix sums of counts, those the
    compiler left without an ``op_name`` too; no decode's scatter-add
    is; no model op is read as an EF layer outside a mixed fusion, and
    an op of the model alone is read as the model."""
    comps, got, lines = tiny_step_v5e
    found = []
    for ins, inside in _timed(comps):
        label = got.labels.get(ins.name)
        assigns = [_assigns(comps, lines[i.name]) for i in inside
                   if i.op == "scatter"]
        if check == "compaction_gathers" and any(
                i.op == "gather" and not (i.own or "").startswith("model")
                for i in inside):
            found.append(ins.name)
            assert label == "ef.compact", (ins.name, label)
            for i in inside:
                if i.op == "gather":
                    # a whole row of 128 a slot
                    assert re.search(r"slice_sizes=\{[\d,]*,128\}",
                                     lines[i.name]), lines[i.name]
        elif check == "compaction_row_counts" and any(
                i.op == "reduce-window"
                or (i.op == "reduce" and i.own == "ef.compact")
                for i in inside):
            found.append(got.rules.get(ins.name))
            assert label == "ef.compact", (ins.name, label)
        elif check == "decode_scatters" and assigns and not any(assigns):
            found.append(got.rules.get(ins.name))
            assert label != "ef.compact", (ins.name, label)
        elif check == "model_not_ef":
            own = {i.own for i in inside if i.own}
            if (label or "").startswith("ef.") and ins.name not in got.mixed:
                assert not any(s.startswith("model") for s in own), ins.name
            if own and all(s.startswith("model") for s in own):
                found.append(got.rules.get(ins.name))
                assert label.startswith("model"), (ins.name, label)
    assert found
    if check == "compaction_gathers":
        # the row form has no loop: none of its gathers runs in a loop body
        instrs = [(comp, i) for comp, (ins, _) in comps.items() for i in ins]
        comp_of = {i.name: comp for comp, i in instrs}
        bodies = {re.search(r"body=%?([\w.\-]+)", lines[i.name]).group(1)
                  for _, i in instrs if i.op == "while"}
        assert not any(comp_of[name] in bodies for name in found), found


# ---------------------------------------------------------------------------
# the expert layer's grouped products at the benchmark's widths
# ---------------------------------------------------------------------------


def test_expert_layer_compiles_at_real_width(one_chip):
    """One expert layer of the DeepSeek-V2-Lite cell (hidden 2048, expert
    width 1408, 8 of 64 experts held, 6 a token, 2 shared, bf16 operands)
    over 4096 tokens, forward and backward, compiled for the described
    chip: megablox's grouped products lower as Mosaic kernels, three in
    the forward pass and three pairs in the backward."""
    from repro.kernels.ef_fused import tuning
    from repro.models import moe
    from repro.models.config import ModelConfig

    cfg = ModelConfig(
        name="dsv2-moe", arch_type="moe", num_layers=1, d_model=2048,
        num_heads=16, num_kv_heads=16, d_ff=10944, vocab_size=12800,
        ffn_pattern=("moe",), num_experts=64, experts_per_token=6,
        num_shared_experts=2, moe_d_ff=1408, capacity_factor=None,
        router_aux="seq", router_aux_coef=0.001, norm_topk_prob=False,
        experts_held=8, expert_operand_dtype="bfloat16").validate()
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda k: moe.init_moe(k, cfg, jnp.float32),
                       jax.random.PRNGKey(0)))
    x = jax.ShapeDtypeStruct((1, 4096, 2048), jnp.float32,
                             sharding=one_chip)

    def loss(p, x):
        out, aux, stats = moe.moe_layer(p, x, cfg)
        return jnp.sum(out) + aux + stats["moe_rows_dropped"]

    with tuning.use_backend("mosaic"):
        fwd = _kernels(loss, params, x)
        both = _kernels(jax.grad(loss, argnums=(0, 1)), params, x)
    assert fwd == 3
    assert both == 9


def _gathers_of(text: str, dims) -> int:
    """All-gathers in compiled ``text`` whose result ends in one of
    ``dims`` (as HLO writes them, e.g. ``4,256,384``: any stacking dims
    may lead)."""
    ends = [re.compile(rf"[\[,]{d}\]") for d in dims]
    return sum(1 for line in text.splitlines()
               for m in [re.search(r"= (\S+) all-gather\(", line)] if m
               and any(e.search(m.group(1)) for e in ends))


@pytest.mark.parametrize("split", [True, False])
def test_expert_layer_keeps_model_split_on_v5e(one_chip, split):
    """The train step of a small expert model on a 2 x 2 (data x model)
    mesh of the described chips: its grouped products lower as Mosaic
    kernels, and no expert stack is gathered over ``model`` (each device
    multiplies its share of the expert width, as ``dist/sharding`` splits
    it).  With the split taken away (the products replicated over
    ``model``), the gathers appear: the check can see them."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.compression import CompressionConfig
    from repro.dist.sharding import train_state_specs
    from repro.kernels.ef_fused import tuning
    from repro.models import init_params, moe
    from repro.models.config import ModelConfig
    from repro.optim import constant, sgd_momentum
    from repro.train import init_train_state, make_train_step

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    cfg = ModelConfig(
        name="moe-split", arch_type="moe", num_layers=1, d_model=256,
        num_heads=2, num_kv_heads=2, d_ff=512, vocab_size=256,
        ffn_pattern=("moe",), num_experts=8, experts_per_token=2,
        num_shared_experts=1, moe_d_ff=384, capacity_factor=None,
        router_aux="seq", norm_topk_prob=False, experts_held=4,
        expert_operand_dtype="bfloat16").validate()
    opt, comp = sgd_momentum(0.9), CompressionConfig(compressor="none")
    state = jax.eval_shape(lambda k: init_train_state(
        init_params(cfg, k), opt, workers=2, model_size=2,
        compression=comp), jax.random.PRNGKey(0))
    state = jax.tree.map(
        lambda s, spec: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
        state, train_state_specs(state, "data"))
    tok = jax.ShapeDtypeStruct((4, 64), jnp.int32,
                               sharding=NamedSharding(mesh, P("data")))
    with pytest.MonkeyPatch.context() as mp:
        if not split:
            mp.setattr(moe, "auto_axes", lambda: {})
        step = make_train_step(cfg, mesh, opt, constant(0.1),
                               compression=comp, remat=False)
        with tuning.use_backend("mosaic"):
            text = step.lower(state, {"tokens": tok, "labels": tok}) \
                .compile().as_text()
    assert text.count("tpu_custom_call") == 9
    gathered = _gathers_of(text, ("4,256,384", "4,384,256"))
    assert (gathered == 0) if split else (gathered > 0), gathered
