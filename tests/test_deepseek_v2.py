"""DeepSeek-V2's blocks in the program against the family's plain
reference (``bench/families/deepseek_v2.py``), on the CPU at small
widths with seeded random weights: latent attention with YaRN, the
expert layer that holds a share of the experts and drops no token, its
counters and its compiled step; the tiny cell through the harness, with
planted errors that the cell's limits catch; and the work the
``moe_gmm_roofline`` reader counts."""
import json
import re
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import compare, harness, reference, spec, system  # noqa: E402
from bench.families import deepseek_v2 as family  # noqa: E402
from bench.tests import tiny  # noqa: E402

CELL = "dsv2lite_moe_1chip"
# the cell's model at small widths: every mechanism kept (a leading
# dense layer, latent attention with YaRN, 8 experts of which the layer
# holds 4, 2 a token, 2 shared, no renormalization, the sequence-wise
# balance loss, bf16 operands in the expert products)
TINY = {
    "name": "tiny-dsv2", "arch_type": "moe", "num_layers": 3,
    "d_model": 64, "num_heads": 4, "num_kv_heads": 4, "d_ff": 128,
    "vocab_size": 256, "rope_theta": 10000.0, "block_pattern": ["mla"],
    "ffn_pattern": ["moe"], "first_dense_layers": 1, "kv_latent_dim": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_scaling_factor": 40.0, "rope_original_max_position": 4096,
    "rope_beta_fast": 32.0, "rope_beta_slow": 1.0, "rope_mscale": 0.707,
    "rope_mscale_all_dim": 0.707, "num_experts": 8, "experts_per_token": 2,
    "num_shared_experts": 2, "moe_d_ff": 32, "capacity_factor": None,
    "router_aux": "seq", "router_aux_coef": 0.001, "norm_topk_prob": False,
    "routed_scaling_factor": 1.0, "experts_held": 4, "expert_offset": 0,
    "expert_operand_dtype": "bfloat16"}
F32 = dict(TINY, expert_operand_dtype="float32")


def _block(model, kind, ffn, seed=0):
    """One layer's weights, made as the benchmark makes them."""
    shapes = family._block_shapes(model, kind, ffn)
    return reference.init_params(shapes, jax.random.PRNGKey(seed))


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("T", [32, 2048])
def test_mla_matches_reference(T):
    """Output and the gradient of every leaf and of the input, at a
    length under and over the query block (``T > 1024`` takes the
    program's blocked path)."""
    from repro.models import mla

    model = dict(F32, num_heads=2, num_kv_heads=2)
    cfg = system.model_config(model)
    p = _block(model, "mla", "mlp")["core"]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, T, model["d_model"]))
    ct = jax.random.normal(jax.random.PRNGKey(2), (1, T, model["d_model"]))

    def f(fn):
        return jax.value_and_grad(
            lambda p, x: jnp.sum(fn(p, x) * ct), argnums=(0, 1))

    with jax.default_matmul_precision("highest"):
        got, (gp, gx) = f(lambda p, x: mla.mla(p, x, cfg))(p, x)
        want, (wp, wx) = f(lambda p, x: family.mla(p, x, model))(p, x)
        out = mla.mla(p, x, cfg)
        ref = family.mla(p, x, model)
    assert _rel(out, ref) < 2e-5
    assert _rel(gx, wx) < 2e-5
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(gp)[0],
                            jax.tree.leaves(wp)):
        assert _rel(a, b) < 2e-5, jax.tree_util.keystr(path)


def test_yarn_is_plain_rope_at_factor_one():
    """With no scaling the rotary frequencies are RoPE's and the softmax
    scale is 1 / sqrt(nope + rope)."""
    from repro.models import mla

    model = dict(F32, rope_scaling_factor=1.0)
    cfg = system.model_config(model)
    d = model["qk_rope_head_dim"]
    want = 1.0 / 10000.0 ** (np.arange(0, d, 2) / d)
    np.testing.assert_allclose(mla.yarn_inv_freq(cfg), want, rtol=1e-6)
    assert mla.softmax_scale(cfg) == pytest.approx(1 / np.sqrt(24))
    # the published scale: mscale(40, 0.707)^2 / sqrt(192)
    full = system.model_config(dict(TINY, qk_nope_head_dim=128,
                                    qk_rope_head_dim=64))
    m = 0.1 * 0.707 * np.log(40) + 1
    assert mla.softmax_scale(full) == pytest.approx(m * m / np.sqrt(192))


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------


def _moe_inputs(model, seed=0):
    p = _block(dict(model, experts_held=model["num_experts"]), "mla",
               "moe", seed)["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (2, 16, model["d_model"]))
    return p, x


def _share(p, lo, hi):
    """The weights a layer holding experts ``[lo, hi)`` is given."""
    return {k: (v[lo:hi] if k in ("w_gate", "w_up", "w_down") else v)
            for k, v in p.items()}


def test_expert_shares_sum_to_the_whole_layer():
    """For each share of ``held`` = 2 of ``E`` = 8 experts: the routed
    parts that the shares give, summed, with the shared expert counted
    once, equal the uncut reference layer."""
    from repro.models import moe

    model = dict(F32, experts_held=2)
    E, G = model["num_experts"], model["experts_held"]
    p, x = _moe_inputs(model)
    shared = family.swiglu(p["shared"], x)
    total = shared
    for off in range(0, E, G):
        cfg = system.model_config(dict(model, expert_offset=off))
        out, _, stats = moe.moe_layer(_share(p, off, off + G), x, cfg)
        total = total + (out - shared)
    with jax.default_matmul_precision("highest"):
        whole, _ = family.moe(p, x, dict(model, experts_held=E))
    assert _rel(total, whole) < 1e-5


def test_dropless_under_forced_imbalance():
    """A router that sends every token to experts 0 and 1: the layer,
    holding experts 0-3, gives the reference's result, drops nothing,
    and its counters read as computed by hand."""
    from repro.models import moe

    model = F32
    E, K, G = (model["num_experts"], model["experts_per_token"],
               model["experts_held"])
    p, x = _moe_inputs(model)
    x = jnp.abs(x) + 1.0                     # every feature positive
    router = -jnp.ones_like(p["router"])
    p = dict(p, router=router.at[:, :K].set(1.0))
    N = x.shape[0] * x.shape[1]
    cfg = system.model_config(model)
    out, _, stats = moe.moe_layer(_share(p, 0, G), x, cfg)
    with jax.default_matmul_precision("highest"):
        want, _ = family.moe(_share(p, 0, G), x, model)
    assert _rel(out, want) < 1e-5
    assert float(stats["moe_rows_dropped"]) == 0.0
    assert float(stats["moe_rows_held"]) == N * K
    # each of the two chosen experts takes all N tokens, against a
    # balanced expert's N K / E
    assert float(stats["moe_load_max"]) == pytest.approx(N / (N * K / E))


def test_grads_with_half_the_experts_held():
    """The gradient of the input and of every weight of a layer holding
    experts 4-7 of 8 against the reference's."""
    from repro.models import moe

    model = dict(F32, expert_offset=4)
    G = model["experts_held"]
    p, x = _moe_inputs(model, seed=3)
    p = _share(p, 4, 4 + G)
    cfg = system.model_config(model)
    ct = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def prog(p, x):
        out, aux, _ = moe.moe_layer(p, x, cfg)
        return jnp.sum(out * ct) + aux

    def ref(p, x):
        out, aux = family.moe(p, x, model)
        return jnp.sum(out * ct) + aux

    with jax.default_matmul_precision("highest"):
        gp, gx = jax.grad(prog, argnums=(0, 1))(p, x)
        wp, wx = jax.grad(ref, argnums=(0, 1))(p, x)
    assert _rel(gx, wx) < 1e-5
    for k in ("w_gate", "w_up", "w_down", "router"):
        assert _rel(gp[k], wp[k]) < 1e-5, k
    assert float(jnp.max(jnp.abs(gp["w_down"]))) > 0


def test_expert_layer_holds_no_scatter():
    """The tiny model's compiled training step: no scatter in a
    ``moe.*`` scope moves rows or gates.  The only ones there build the
    grouped kernel's tile metadata (megablox's ``jnp.repeat`` and
    ``jnp.histogram``): int32 vectors of at most ``tiles + E``
    entries."""
    from repro.data import batch_for
    from repro.models import init_params, loss_fn

    cfg = system.model_config(TINY)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = batch_for(cfg, 0, global_batch=2, seq_len=32)
    step = jax.jit(jax.grad(lambda p, b: loss_fn(p, cfg, b)[0]))
    text = step.lower(params, batch).compile().as_text()
    rows = 2 * 32 * TINY["experts_per_token"]
    bound = -(-rows // 128) + TINY["num_experts"]
    found = 0
    for line in text.splitlines():
        m = re.search(r"= (\w+)\[([\d,]*)\]\S* scatter\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if not m or not name or "moe." not in name.group(1):
            continue
        found += 1
        assert re.search(r"/jit\(t?gmm\)/", name.group(1)), line
        assert m.group(1) == "s32", line
        assert int(np.prod([int(n) for n in m.group(2).split(",")])) \
            <= bound, line
    assert found                  # the kernel's metadata is still seen


# ---------------------------------------------------------------------------
# the tiny cell through the harness, and planted errors
# ---------------------------------------------------------------------------


def _cell():
    """The cell as ``BENCHMARK.json`` lists it: its model, job, family
    and limits."""
    return spec.load(CELL)


def _job():
    """The cell's job with the stand-ins' sequence, ratio and rate
    (``bench/tests/tiny.py``)."""
    full = _cell()["job"]
    return dict(full, seq=32, ratio=0.01, lr=0.01)


def _limits():
    return _cell()["limits"]


def test_config_file_holds_the_catalog_row():
    """The configuration's file holds every key of the published row at
    its top level, as published but for the keys it lists in
    ``reduced``, which ``BENCHMARK.json`` lists too; ``config`` is the
    same row as cut."""
    with open(spec.ROOT / "BENCHMARK.json") as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}[
            "deepseek-v2-lite-L5-EP8"]
    with open(spec.ROOT / entry["file"]) as f:
        conf = json.load(f)
    assert entry["source"] == conf["source"]
    assert entry["reduced"] == conf["reduced"]
    assert conf["config"] == {k: conf[k] for k in conf["published"]}
    changed = [k for k, v in conf["published"].items() if conf[k] != v]
    assert sorted(changed) == sorted(conf["reduced"])


def test_tiny_cell_is_correct(tmp_path):
    """The tiny model with the cell's job, limits and family, as new
    files under a root of their own, through ``harness.run``."""
    with open(spec.ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    job = {k: v for k, v in _job().items()
           if k not in ("workers", "model_size")}
    files = {
        "BENCHMARK.json": dict(
            bench, configs=[{"name": "tiny-dsv2",
                             "file": "bench/configs/tiny-dsv2.json"}],
            workloads=[{"name": CELL, "config": "tiny-dsv2",
                        "traffic": "tiny_job", "chips": 1}],
            per_layer=[]),
        "bench/configs/tiny-dsv2.json": {"name": "tiny-dsv2",
                                         "family": "deepseek_v2",
                                         "model": TINY},
        "bench/jobs/tiny_job.json": job,
        f"bench/limits/{CELL}.json": {"limits": _limits()},
    }
    for name, data in files.items():
        (tmp_path / name).parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / name).write_text(json.dumps(data))
    (tmp_path / "bench/families").mkdir()
    (tmp_path / "bench/families/deepseek_v2.py").write_text(
        Path(family.__file__).read_text())
    cell = spec.load(CELL, tmp_path)
    out = harness.run(cell, 2 ** 31 + 41, 0.2, False,
                      t0=time.perf_counter())
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["counters"]["ef_leaves_at_cap"] >= 0


SEED = 2 ** 31 + 7


@pytest.fixture(scope="module")
def tiny_reference():
    return reference.first_steps(family, TINY, _job(), SEED,
                                 n_steps=_job()["check_steps"])


def _shared_dropped(mp):
    from repro.models import moe

    mp.setattr(moe, "mlp", lambda p, x: jnp.zeros_like(x))


@pytest.mark.parametrize("error", ["renormalized_gates", "no_mscale",
                                   "no_shared_expert"])
def test_planted_errors_fail_a_limit(error, tiny_reference):
    """The program with an error planted, against the sound reference:
    at least one of the cell's limits refuses it."""
    model = {"renormalized_gates": dict(TINY, norm_topk_prob=True),
             "no_mscale": dict(TINY, rope_mscale=0.0,
                               rope_mscale_all_dim=0.0),
             "no_shared_expert": TINY}[error]
    with pytest.MonkeyPatch.context() as mp:
        if error == "no_shared_expert":
            _shared_dropped(mp)
        sut = system.System(model, _job(), SEED)
        _, prog, _ = harness.first_steps(sut, _job()["check_steps"])
    correct, checks = compare.verdict(
        compare.readings(prog, tiny_reference), _limits())
    assert not correct, checks


# ---------------------------------------------------------------------------
# the roofline reader's work
# ---------------------------------------------------------------------------


def _reader():
    return spec.module(spec.HERE / "metrics" / "moe_gmm_roofline.py",
                       "bench_metric_")


def test_roofline_work_of_the_cell():
    """At the cell's 4096 tokens: 3072 rows a layer reach the 8 held
    experts; 4 layers x 3 matrices x 3 products of 2 R D F FLOPs; bytes
    as the reader's docstring counts them."""
    cell = _cell()
    model, mod = cell["model"], _reader()
    R, D, F = 3072, 2048, 1408
    P = 8 * D * F
    assert mod.routed_rows(model, 4096) == R
    assert mod.flops(model, 4096) == 4 * 3 * 3 * 2 * R * D * F
    one = 3 * (8 * P + 8 * R * (D + F))
    assert mod.hbm_bytes(model, 4096) == 4 * one


def test_roofline_reads_a_trace():
    """The share from a summary of 2 ms of Mosaic time a step; None
    where the step ran no Mosaic kernel, and None where the longest ops
    show a Mosaic kernel that is not a grouped product."""
    from bench import peaks, trace

    mod = _reader()
    cell = _cell()
    ctx = {"model": cell["model"], "job": cell["job"], "chips": 1,
           "steps": 3, "peaks": peaks.peaks_of("TPU v5 lite"),
           "summary": trace.Summary(busy=[1.0],
                                    by_class=[{"mosaic": 6e-3}])}
    need = max(mod.flops(cell["model"], 4096) / 197e12,
               mod.hbm_bytes(cell["model"], 4096) / 819e9)
    assert mod.read(ctx) == pytest.approx(100 * need / 2e-3)
    gmm_ms = spec.module(spec.HERE / "metrics" / "moe_gmm_ms.py",
                         "bench_metric_")
    assert gmm_ms.read(ctx) == pytest.approx(2.0)
    ctx["summary"].top_ops = [["step:fusion.3", 0.5],
                              ["mosaic:gmm.6", 2e-3], ["mosaic:tgmm.2", 1e-3]]
    assert gmm_ms.read(ctx) == pytest.approx(2.0)
    ctx["summary"].top_ops.append(["mosaic:flash_attention.1", 1e-3])
    assert mod.read(ctx) is None and gmm_ms.read(ctx) is None
    ctx["summary"] = trace.Summary(busy=[1.0], by_class=[{"step": 1.0}])
    assert mod.read(ctx) is None
    assert gmm_ms.read(ctx) is None


def test_family_flops_count_routed_experts_only():
    """Forward FLOPs: every weight matrix but the embedding twice, expert
    stacks for the ``K held / E`` experts a token reaches here; the
    scores and values over the full sequence."""
    m, S = TINY, 32
    D, H, V = m["d_model"], m["num_heads"], m["vocab_size"]
    r, nope, rope, vd = 32, 16, 8, 16
    attn = D * H * (nope + rope) + D * (r + rope) + r * H * (nope + vd) \
        + H * vd * D
    dense = 3 * D * m["d_ff"]
    experts = 3 * D * m["moe_d_ff"] * 4 * 2 / 8
    shared = 3 * D * 2 * m["moe_d_ff"]
    router = D * 8
    weights = 3 * attn + dense + 2 * (experts + shared + router) + D * V
    want = 2.0 * weights + 3 * 2.0 * S * H * (nope + rope + vd)
    assert family.forward_flops_per_token(m, S) == pytest.approx(want)


def test_tiny_stands_for_the_cell():
    """The stand-in keeps the cell's model kind: the same keys, family
    and mechanisms, at small widths."""
    cell = _cell()
    assert set(TINY) == set(cell["model"]) - {"source"}
    for key in ("block_pattern", "ffn_pattern", "first_dense_layers",
                "router_aux", "norm_topk_prob", "capacity_factor",
                "expert_operand_dtype"):
        assert TINY[key] == cell["model"][key], key
    assert cell["family"].__name__.endswith("deepseek_v2")
    assert tiny.limits(CELL) == _limits()


def test_preset_runs_the_cells_arithmetic():
    """The ``deepseek-v2-lite`` preset that ``launch/train.py --arch``
    runs is the cell's model before its cut: every field the cell sets
    is the preset's (the expert products' operand dtype included), but
    the depth, the held experts and the vocabulary rows."""
    from repro.configs import get_config

    preset = get_config("deepseek-v2-lite")
    cut = {"name", "source", "num_layers", "experts_held", "vocab_size"}
    for key, value in _cell()["model"].items():
        if key not in cut:
            got = getattr(preset, key)
            assert (list(got) if isinstance(got, tuple) else got) == value, \
                key
    assert preset.held_experts == preset.num_experts == 64
