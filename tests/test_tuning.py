"""Backend resolution + KernelConfig autotune tests (DESIGN.md §15).

Pins the ISSUE 10 acceptance rules:

* platform matrix — ``resolve_backend(None)`` picks mosaic on TPU,
  triton on GPU, the interpreter on CPU, and raises on any other
  platform; mosaic never runs interpreted; an explicit ``backend=``
  always wins; the legacy ``interpret=`` bool still works behind
  exactly ONE ``DeprecationWarning`` per process;
* per-dtype block minima — derived from (backend, dtype): mosaic one
  full TPU tile (f32 1024, bf16 2048), triton a 4 KiB coalesced
  segment (f32 1024, bf16 2048), interpreter the legacy 2048 floor
  for every dtype (committed CPU baselines must not churn);
* config resolution ladder — checked-in table beats autotune, the
  in-process cache makes the second resolve free (a stub timer counts
  measurement calls), and resolution is deterministic.
"""
import json
import os
import warnings

import jax
import pytest

from repro.kernels.ef_fused import ops, tuning
from repro.kernels.ef_fused.tuning import (
    INTERPRET_MIN_BLOCK, KernelConfig, choose_block, choose_stats_block,
    exec_interpret, min_block, resolve_backend, resolve_config,
    shape_class, use_backend)


@pytest.fixture(autouse=True)
def _isolated(monkeypatch):
    """Each test sees a clean cache, no env override, a fresh warn flag."""
    monkeypatch.delenv(tuning.ENV_BACKEND, raising=False)
    monkeypatch.delenv(tuning.ENV_TABLE_DIR, raising=False)
    tuning.clear_cache()
    warned = tuning._INTERPRET_WARNED
    yield
    tuning.clear_cache()
    tuning._INTERPRET_WARNED = warned


# ---------------------------------------------------------------------------
# backend resolution matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("platform,want", [
    ("tpu", "mosaic"), ("gpu", "triton"), ("cuda", "triton"),
    ("rocm", "triton"), ("cpu", "interpret")])
def test_platform_default_matrix(monkeypatch, platform, want):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert resolve_backend(None, None) == want
    assert resolve_backend(None, None, platform=platform) == want


@pytest.mark.parametrize("platform", ["tpu", "gpu", "cpu"])
def test_explicit_backend_wins(monkeypatch, platform):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    monkeypatch.setenv(tuning.ENV_BACKEND, "mosaic")
    with use_backend("interpret"):
        assert resolve_backend("triton", None) == "triton"


def test_env_and_context_override(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    monkeypatch.setenv(tuning.ENV_BACKEND, "triton")
    assert resolve_backend(None, None) == "triton"
    with use_backend("mosaic"):           # context beats env
        assert resolve_backend(None, None) == "mosaic"
    assert resolve_backend(None, None) == "triton"
    with pytest.raises(ValueError, match="unknown kernel backend"):
        with use_backend("bogus"):
            pass
    monkeypatch.setenv(tuning.ENV_BACKEND, "bogus")
    with pytest.raises(ValueError, match=tuning.ENV_BACKEND):
        resolve_backend(None, None)


def test_interpret_kwarg_shim_warns_exactly_once(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    tuning._INTERPRET_WARNED = False
    with pytest.warns(DeprecationWarning, match="interpret= kwarg"):
        assert resolve_backend(None, True) == "interpret"
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        # second use: same result, no second warning
        assert resolve_backend(None, False) == "triton"
        assert resolve_backend(None, True) == "interpret"
    assert not [w for w in caught
                if issubclass(w.category, DeprecationWarning)]
    # explicit backend= silences the shim entirely
    assert resolve_backend("mosaic", True) == "mosaic"


def test_exec_interpret_matrix():
    assert exec_interpret("interpret", "tpu")
    assert exec_interpret("interpret", "gpu")
    assert not exec_interpret("mosaic", "tpu")
    assert not exec_interpret("mosaic", "cpu")  # compiles or raises
    assert not exec_interpret("triton", "gpu")
    assert exec_interpret("triton", "cpu")      # the CI smoke leg


def test_unknown_platform_raises(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "metal")
    with pytest.raises(ValueError, match="no kernel backend"):
        resolve_backend(None, None)
    with pytest.raises(ValueError, match="no kernel backend"):
        tuning.default_backend("metal")


def test_mosaic_off_tpu_raises_instead_of_interpreting():
    """backend="mosaic" lowers for the TPU only: on the CPU it fails to
    compile rather than running the kernels under the interpreter."""
    import jax.numpy as jnp
    from repro.kernels.ef_fused import fused_compress_ef
    g = jnp.ones((4096,), jnp.float32)
    with pytest.raises(ValueError, match="interpret mode"):
        fused_compress_ef(g, None, "gaussiank", 40, backend="mosaic")


# ---------------------------------------------------------------------------
# per-dtype block minima + heuristic edge cases
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend,dtype,want", [
    ("mosaic", "float32", 1024), ("mosaic", "bfloat16", 2048),
    ("triton", "float32", 1024), ("triton", "bfloat16", 2048),
    ("interpret", "float32", INTERPRET_MIN_BLOCK),
    ("interpret", "bfloat16", INTERPRET_MIN_BLOCK)])
def test_min_block_per_dtype(backend, dtype, want):
    assert min_block(backend, dtype) == want


@pytest.mark.parametrize("backend", tuning.BACKENDS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("d", [1, 33, 257, 5000, 2 ** 22 + 1])
def test_choose_block_edges(backend, dtype, d):
    """Odd d, bf16, d == 1: the block is always a pow2 multiple of the
    (backend, dtype) floor and the interpreter grid stays bounded."""
    block = choose_block(d, backend, dtype)
    base = min_block(backend, dtype)
    assert block >= base and block % base == 0
    assert (block & (block - 1)) == 0           # power of two
    if backend == "interpret":
        nblocks = -(-d // block)
        assert nblocks <= tuning.MAX_INTERPRET_BLOCKS
    stats = choose_stats_block(d, backend, dtype)
    assert stats >= base and (stats & (stats - 1)) == 0
    if backend == "interpret":
        assert -(-d // stats) <= tuning.MAX_INTERPRET_STATS_BLOCKS


def test_interpret_floor_matches_legacy_cpu_policy():
    """The committed CPU baselines were produced under the legacy 2048
    floor — the shim must reproduce it bit-for-bit."""
    assert ops.MIN_BLOCK == 2048
    for d in (257, 2048, 5000, 65536, 2 ** 20):
        assert ops.choose_block(d, True) == choose_block(d, "interpret")
        assert ops.choose_stats_block(d, True) == \
            choose_stats_block(d, "interpret")


def test_shape_class():
    assert shape_class(1) == 1
    assert shape_class(2) == 2
    assert shape_class(5000) == 8192
    assert shape_class(8192) == 8192
    assert shape_class(8193) == 16384


# ---------------------------------------------------------------------------
# resolution ladder: cache, stub-timed autotune, checked-in table
# ---------------------------------------------------------------------------


def _counting_timer(calls):
    def timer(cfg, d, dtype, iters=5):
        calls.append(cfg)
        # deterministic scoring: prefer the largest block, 8 warps
        return 1.0 / (cfg.block * (2 if cfg.num_warps == 8 else 1))
    return timer


def test_autotune_cache_determinism():
    calls = []
    timer = _counting_timer(calls)
    cfg1 = resolve_config(5000, backend="triton", measure=True, timer=timer)
    n_first = len(calls)
    assert n_first == len(tuning.candidates("triton", 5000))
    assert cfg1.source == "autotune" and cfg1.backend == "triton"
    # cache hit: same shape-class resolves with ZERO further timing
    cfg2 = resolve_config(4097, backend="triton", measure=True, timer=timer)
    assert len(calls) == n_first
    assert cfg2 == cfg1
    # a different shape-class re-measures
    resolve_config(2 ** 14, backend="triton", measure=True, timer=timer)
    assert len(calls) > n_first
    # determinism: a cleared cache re-derives the identical winner
    tuning.clear_cache()
    cfg3 = resolve_config(5000, backend="triton", measure=True,
                          timer=_counting_timer([]))
    assert cfg3 == cfg1


def test_interpreter_resolution_never_measures(tmp_path, monkeypatch):
    monkeypatch.setenv(tuning.ENV_TABLE_DIR, str(tmp_path))  # no table
    calls = []
    cfg = resolve_config(65536, backend="interpret",
                         timer=_counting_timer(calls))
    assert calls == [] and cfg.source == "heuristic"
    assert cfg.block == choose_block(65536, "interpret")


def test_candidate_grid_shape():
    cands = tuning.candidates("triton", 2 ** 16)
    assert all(c.backend == "triton" for c in cands)
    assert {c.num_warps for c in cands} == {4, 8}
    blocks = {c.block for c in cands}
    assert min(blocks) == min_block("triton", "float32")
    assert max(blocks) <= shape_class(2 ** 16)
    # a leaf below the floor still gets at least the floor candidate
    tiny = tuning.candidates("mosaic", 7)
    assert [c.block for c in tiny] == [min_block("mosaic", "float32")]


def test_table_consulted_before_autotune(tmp_path, monkeypatch):
    pinned = KernelConfig("triton", 4096, 8192, num_warps=8)
    table = {"schema": tuning.TABLE_SCHEMA, "platform": "cpu",
             "configs": {tuning.config_key("triton", 5000, "float32"):
                         pinned.to_dict()}}
    path = tmp_path / "kernelconfig.cpu.json"
    path.write_text(json.dumps(table))
    monkeypatch.setenv(tuning.ENV_TABLE_DIR, str(tmp_path))
    tuning.clear_cache()
    assert tuning.table_path("cpu") == str(path)
    calls = []
    cfg = resolve_config(5000, backend="triton", platform="cpu",
                         measure=True, timer=_counting_timer(calls))
    assert calls == []                 # table hit: no timing at all
    assert cfg.source == "table"
    assert (cfg.block, cfg.stats_block, cfg.num_warps) == (4096, 8192, 8)
    # a key NOT in the table falls through to the stub-timed autotune
    cfg2 = resolve_config(2 ** 16, backend="triton", platform="cpu",
                          measure=True, timer=_counting_timer(calls))
    assert calls and cfg2.source == "autotune"


def test_table_schema_mismatch_is_loud(tmp_path, monkeypatch):
    path = tmp_path / "kernelconfig.cpu.json"
    path.write_text(json.dumps({"schema": "bogus/v0", "configs": {}}))
    monkeypatch.setenv(tuning.ENV_TABLE_DIR, str(tmp_path))
    tuning.clear_cache()
    with pytest.raises(ValueError, match="unexpected schema"):
        resolve_config(5000, backend="triton", platform="cpu",
                       measure=False)


def test_checked_in_cpu_table_is_valid():
    """The committed benchmarks/baselines/kernelconfig.cpu.json parses,
    carries the right schema, and its configs match what the heuristic
    derives today (the CPU table is heuristic by construction)."""
    path = tuning.table_path("cpu")
    assert os.path.exists(path), path
    with open(path) as f:
        data = json.load(f)
    assert data["schema"] == tuning.TABLE_SCHEMA
    assert data["platform"] == "cpu"
    assert "env" in data
    for key, cfg_dict in data["configs"].items():
        backend, dtype, sclass = key.split("/")
        cfg = KernelConfig.from_dict(cfg_dict)
        assert cfg.backend == backend
        want = tuning.heuristic_config(backend, int(sclass), dtype)
        assert (cfg.block, cfg.stats_block) == (want.block,
                                                want.stats_block)


def test_kernelconfig_roundtrip_ignores_unknown_keys():
    cfg = KernelConfig("mosaic", 1024, 4096, bcap_slack=1.5)
    d = cfg.to_dict()
    d["future_field"] = 7              # forward-compat: extra keys skip
    assert KernelConfig.from_dict(d) == cfg


# ---------------------------------------------------------------------------
# ops-layer plumbing: _resolve honors the ladder, shims stay exact
# ---------------------------------------------------------------------------


def test_ops_resolve_explicit_blocks_skip_ladder(monkeypatch):
    """Explicit block/stats_block kwargs must not consult table or
    cache (source == 'explicit')."""
    import jax.numpy as jnp
    g = jnp.zeros((4096,))
    d, k_cap, block, stats, bcap, cfg = ops._resolve(
        g, None, "gaussiank", 40, None, 2048, 4096, None, None,
        backend="interpret")
    assert (block, stats) == (2048, 4096)
    assert cfg.source == "explicit" and cfg.backend == "interpret"


def test_ops_resolve_uses_config_ladder(tmp_path, monkeypatch):
    import jax.numpy as jnp
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    g = jnp.zeros((65536,))
    *_, cfg = ops._resolve(g, None, "gaussiank", 100, None, None, None,
                           None, None)
    # with the committed table in place the ladder stops at "table";
    # either way the resolved geometry equals the legacy CPU heuristic
    assert cfg.backend == "interpret" and cfg.source in ("table",
                                                         "heuristic")
    assert cfg.block == choose_block(65536, "interpret")
    monkeypatch.setenv(tuning.ENV_TABLE_DIR, str(tmp_path))  # no table
    tuning.clear_cache()
    *_, cfg2 = ops._resolve(g, None, "gaussiank", 100, None, None, None,
                            None, None)
    assert cfg2.source == "heuristic"
    assert (cfg2.block, cfg2.stats_block) == (cfg.block, cfg.stats_block)
