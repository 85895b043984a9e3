"""A configuration brings its architecture to the harness as a family
(``bench/families/<family>.py``) that its file names: the decoder gives
the numbers the harness gave before families; a second family, added
under another root as new files only, runs a cell through the harness;
and the program is given every key the configuration sets."""
import json
import shutil
import time
from pathlib import Path

import jax
import pytest

from bench import harness, reference, spec, system
from bench.tests import moe_family, tiny

HERE = Path(__file__).resolve().parent
CELL = "tiny_moe_1chip"


def test_decoder_gives_the_parents_steps():
    """The reference's first steps of the decoder's stand-in cell, to the
    last bit of what the harness's reference gave before families
    (``decoder_tiny_steps.json``, recorded on the CPU)."""
    with open(HERE / "decoder_tiny_steps.json") as f:
        want = json.load(f)
    cell = tiny.cell(want["cell"])
    got = reference.first_steps(cell["family"], cell["model"], cell["job"],
                                want["seed"], n_steps=3)
    for key in ("loss", "budgets", "grad_norms", "update_norms",
                "update_counts", "first_change_norms", "change_norms"):
        assert [float(x) for x in got[key]] == want[key], key


def test_model_config_passes_moe_fields():
    model = dict(tiny.MOE, sliding_window=64)
    cfg = system.model_config(model)
    for key, value in model.items():
        want = tuple(value) if isinstance(value, list) else value
        assert getattr(cfg, key) == want, key


def test_model_config_refuses_unknown_key():
    with pytest.raises(KeyError, match="kv_lora_rank"):
        system.model_config(dict(tiny.DENSE, kv_lora_rank=512))


def test_moe_family_weights_in_program_layout():
    from repro.models import init_params

    key = jax.random.PRNGKey(0)
    ours = jax.eval_shape(lambda k: reference.init_params(
        moe_family.param_shapes(tiny.MOE), k), key)
    theirs = jax.eval_shape(
        lambda k: init_params(system.model_config(tiny.MOE), k), key)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert [x.shape for x in jax.tree.leaves(ours)] == \
        [x.shape for x in jax.tree.leaves(theirs)]


def _root(tmp_path: Path, family) -> Path:
    """A benchmark root of its own holding one cell, the MoE stand-in:
    ``BENCHMARK.json`` and, under ``bench/``, its configuration, job,
    limits and (unless ``family`` is None) family, each a new file."""
    with open(spec.ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    conf = {"name": "tiny-moe", "model": tiny.MOE}
    if family is not None:
        conf["family"] = family
    job = {k: v for k, v in tiny.cell("stablelm_efjnp_1chip")["job"].items()
           if k not in ("workers", "model_size")}
    files = {
        "BENCHMARK.json": dict(
            bench, configs=[{"name": "tiny-moe",
                             "file": "bench/configs/tiny-moe.json"}],
            workloads=[{"name": CELL, "config": "tiny-moe",
                        "traffic": "tiny_moe_job", "chips": 1}],
            per_layer=[]),
        "bench/configs/tiny-moe.json": conf,
        "bench/jobs/tiny_moe_job.json": job,
        "bench/limits/tiny_moe_1chip.json": {"limits": tiny.limits(
            "stablelm_efjnp_1chip")},
    }
    for name, data in files.items():
        (tmp_path / name).parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / name).write_text(json.dumps(data))
    (tmp_path / "bench/families").mkdir()
    shutil.copy(HERE / "moe_family.py", tmp_path / "bench/families/moe.py")
    return tmp_path


def _files(root: Path) -> dict:
    """(size, mtime) of the benchmark's files under ``root``, the
    interpreter's byte-code caches left out."""
    found = [root / "BENCHMARK.json"] + [
        p for p in (root / "bench").rglob("*")
        if p.is_file() and "__pycache__" not in p.parts]
    return {p: (p.stat().st_size, p.stat().st_mtime_ns) for p in found}


def test_second_family_runs_a_cell(tmp_path):
    """The MoE family, its configuration, job and limits as new files
    under a root of their own: the cell runs through the harness on the
    CPU, the reference's first steps taken with the new family, and no
    file of the repository's benchmark is touched."""
    root = _root(tmp_path, "moe")
    before = _files(spec.ROOT)
    cell = spec.load(CELL, root)
    assert Path(cell["family"].__file__) == root / "bench/families/moe.py"
    out = harness.run(cell, 2 ** 31 + 23, 0.2, False,
                      t0=time.perf_counter())
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert _files(spec.ROOT) == before


@pytest.mark.parametrize("family,error", [
    (None, KeyError), ("decoder_v9", FileNotFoundError),
    ("../moe", ValueError)])
def test_family_is_named_or_refused(tmp_path, family, error):
    with pytest.raises(error):
        spec.load(CELL, _root(tmp_path, family))


def test_moe_family_counts_routed_experts_only():
    """Forward FLOPs of the MoE stand-in: the decoder's count of its
    dense parts plus ``experts_per_token`` of the 4 experts."""
    from bench.families import decoder

    m, S = tiny.MOE, 32
    D, F = m["d_model"], m["moe_d_ff"]
    dense = dict(m, ffn_pattern=["mlp", "none"])
    router = D * m["num_experts"]
    experts = 3 * D * F * m["experts_per_token"]
    shared = 3 * D * F * m["num_shared_experts"]
    want = (decoder.forward_flops_per_token(dense, S)
            + 2.0 * (router + experts + shared))
    assert moe_family.forward_flops_per_token(m, S) == pytest.approx(
        want, rel=1e-12)
