"""Public-API pins for the consolidated compression surface (ISSUE 8).

Three families of contract:

* :class:`CompressionConfig` — the ONE frozen config object every
  consumer (per-leaf, bucketed, chunked, publisher, train factories)
  takes: defaults, immutability, validation, ``replace`` round-trip.
* :class:`AggregateResult` — the named result all three ``aggregate_*``
  functions return: field names, order (positional-compatible with the
  historical 5-tuple), and that config-first and legacy-kwarg calls
  produce identical numbers.
* Deprecation shims — loose legacy kwargs and ``hierarchical=True``
  still work but warn, and mixing them with a config is a TypeError.
  Signatures are pinned with ``inspect`` so a silent rename/reorder of
  the public entry points fails here, not in a downstream caller.
"""
import dataclasses
import inspect
import warnings

import pytest

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import get_compressor
from repro.core.adaptk import make_policy
from repro.core.compression import (DENSE, STRATEGIES, CompressionConfig,
                                    as_config)
from repro.dist import aggregate
from repro.dist.aggregate import AggregateResult
from repro.launch.mesh import make_mesh

MSIZE, RATIO = 2, 0.1


# ---------------------------------------------------------------------------
# CompressionConfig
# ---------------------------------------------------------------------------


def test_config_defaults():
    c = CompressionConfig()
    assert c.compressor == "gaussiank"
    assert c.ratio == 0.001
    assert c.strategy == "allgather"
    assert c.codec_dtype is None
    assert c.momentum_correction == 0.0
    assert c.backend == "auto"
    assert c.density_policy is None
    assert c.chunks == 1
    assert not c.dense
    assert not c.adaptive
    assert c.spec.name == "gaussiank"


def test_config_is_frozen_and_hashable():
    c = CompressionConfig()
    with pytest.raises(dataclasses.FrozenInstanceError):
        c.ratio = 0.5
    # hashable => usable as a jit static argument (serve/publish.py)
    assert hash(c) == hash(CompressionConfig())


def test_config_replace_round_trip():
    c = CompressionConfig(compressor="topk", ratio=0.05)
    d = c.replace(strategy="gtopk")
    assert d.strategy == "gtopk" and d.compressor == "topk"
    assert c.strategy == "allgather"  # original untouched
    assert d.replace(strategy="allgather") == c


def test_config_validation():
    with pytest.raises(ValueError, match="strategy"):
        CompressionConfig(strategy="ring")
    with pytest.raises(ValueError, match="backend"):
        CompressionConfig(backend="tpu")
    with pytest.raises(ValueError, match="ratio"):
        CompressionConfig(ratio=0.0)
    with pytest.raises(ValueError, match="ratio"):
        CompressionConfig(ratio=1.5)
    with pytest.raises(ValueError, match="chunks"):
        CompressionConfig(chunks=0)
    with pytest.raises(ValueError, match="momentum_correction"):
        CompressionConfig(momentum_correction=1.0)
    with pytest.raises(KeyError, match="unknown compressor"):
        CompressionConfig(compressor="nope")
    with pytest.raises(TypeError, match="DensityPolicy"):
        CompressionConfig(density_policy="variance")


def test_config_dense_semantics():
    c = CompressionConfig(compressor="none")
    assert c.dense and c.compressor == DENSE and c.spec is None
    # a None compressor normalizes to the dense spelling
    assert CompressionConfig(compressor=None).dense
    with pytest.raises(ValueError, match="density_policy"):
        CompressionConfig(compressor="none",
                          density_policy=make_policy("variance"))
    with pytest.raises(ValueError, match="momentum_correction"):
        CompressionConfig(compressor="none", momentum_correction=0.5)


def test_as_config():
    assert as_config(None) == CompressionConfig()
    c = CompressionConfig(compressor="topk", ratio=0.1)
    assert as_config(c) is c
    with pytest.raises(TypeError, match="CompressionConfig"):
        as_config({"compressor": "topk"})


def test_strategies_vocabulary():
    assert set(STRATEGIES) == {"allgather", "gtopk", "hierarchical",
                               "hier_gtopk"}


# ---------------------------------------------------------------------------
# AggregateResult + config-vs-legacy equality
# ---------------------------------------------------------------------------


def test_aggregate_result_fields():
    assert AggregateResult._fields == ("agg", "resid", "resid2",
                                       "adapt_state", "metrics")


def _grads():
    k = jax.random.PRNGKey(0)
    return {"w": 0.01 * jax.random.normal(k, (33, 5)),
            "b": 0.01 * jax.random.normal(jax.random.fold_in(k, 1), (7,))}


def _run_per_leaf(call):
    """Run an aggregate_compressed spelling on the (1,1) mesh (the
    per-leaf path needs a live data axis, like tests/test_layout.py)."""
    mesh = make_mesh((1, 1), ("data", "model"))
    grads = _grads()
    resid = aggregate.init_residuals(grads, MSIZE)
    body = lambda g, e: call(g, e)  # noqa: E731
    sm = jax.shard_map(body, mesh=mesh, in_specs=(P(), P()),
                       out_specs=P(), axis_names={"data"},
                       check_vma=False)
    return jax.jit(sm)(grads, resid)


def test_config_call_matches_legacy_call():
    """The config-first spelling and the deprecated loose-kwarg spelling
    must produce identical numbers (the shim only repackages)."""
    config = CompressionConfig(compressor="topk", ratio=RATIO,
                               backend="reference")
    key = jax.random.PRNGKey(3)
    res = _run_per_leaf(lambda g, e: aggregate.aggregate_compressed(
        g, e, config, ("data",), "model", MSIZE, key, world=1))
    assert isinstance(res, AggregateResult)
    with pytest.warns(DeprecationWarning, match="aggregate_compressed"):
        legacy = _run_per_leaf(lambda g, e: aggregate.aggregate_compressed(
            g, e, get_compressor("topk"), RATIO, ("data",), "model", MSIZE,
            key, world=1, backend="reference"))
    for name in ("w", "b"):
        np.testing.assert_array_equal(np.asarray(res.agg[name]),
                                      np.asarray(legacy.agg[name]))
        np.testing.assert_array_equal(np.asarray(res.resid[name]),
                                      np.asarray(legacy.resid[name]))
    # positional unpacking still works (NamedTuple 5-tuple compatibility)
    agg, resid, resid2, adapt_state, metrics = res
    assert resid2 is None and adapt_state is None
    assert "density" in metrics


def test_config_path_rejects_legacy_kwargs():
    config = CompressionConfig(compressor="topk", ratio=RATIO)
    with pytest.raises(TypeError, match="legacy kwargs"):
        aggregate.aggregate_compressed(
            _grads(), None, config, ("data",), "model", MSIZE,
            jax.random.PRNGKey(0), strategy="gtopk")


def test_legacy_path_rejects_unknown_kwargs():
    with pytest.warns(DeprecationWarning), \
            pytest.raises(TypeError, match="unexpected"):
        aggregate.aggregate_compressed(
            _grads(), None, get_compressor("topk"), RATIO, ("data",),
            "model", MSIZE, jax.random.PRNGKey(0), ratioo=0.5)


def test_dense_config_rejected_by_aggregate():
    with pytest.raises(ValueError, match="aggregate_dense"):
        aggregate.aggregate_compressed(
            _grads(), None, CompressionConfig(compressor="none"),
            ("data",), "model", MSIZE, None)


# ---------------------------------------------------------------------------
# deprecation shims
# ---------------------------------------------------------------------------


def test_resolve_strategy_hierarchical_flag_warns():
    with pytest.warns(DeprecationWarning, match="hierarchical=True"):
        assert aggregate.resolve_strategy("allgather", True) == \
            "hierarchical"
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        # explicit strategies pass through silently; flag never demotes
        assert aggregate.resolve_strategy("gtopk") == "gtopk"
    with pytest.warns(DeprecationWarning):
        assert aggregate.resolve_strategy("gtopk", True) == "gtopk"
    with pytest.raises(ValueError, match="strategy"):
        aggregate.resolve_strategy("ring")


def test_init_train_state_legacy_kwargs_warn():
    from repro.optim import sgd_momentum
    from repro.train import init_train_state

    params = {"w": jnp.ones((8,))}
    with pytest.warns(DeprecationWarning, match="init_train_state"):
        st = init_train_state(params, sgd_momentum(0.9), workers=2,
                              model_size=1, strategy="hierarchical")
    assert "resid2" in st
    # config-first spelling of the same thing, no warning
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        st2 = init_train_state(
            params, sgd_momentum(0.9), workers=2, model_size=1,
            compression=CompressionConfig(strategy="hierarchical"))
    assert jax.tree.structure(st) == jax.tree.structure(st2)


def test_make_train_step_legacy_kwargs_warn():
    from repro.optim import sgd_momentum
    from repro.train import make_train_step

    mesh = make_mesh((1, 1), ("data", "model"))
    loss = lambda p, b: (jnp.sum(p["w"] * b), {})  # noqa: E731
    with pytest.warns(DeprecationWarning, match="make_train_step"):
        make_train_step(None, mesh, sgd_momentum(0.9), lambda s: 0.1,
                        compressor="topk", ratio=0.1, loss_fn=loss,
                        remat=False)


def test_train_factories_reject_config_plus_legacy():
    from repro.optim import sgd_momentum
    from repro.train import init_train_state, make_train_step

    config = CompressionConfig(compressor="topk", ratio=0.1)
    with pytest.raises(TypeError, match="CompressionConfig"):
        init_train_state({"w": jnp.ones((8,))}, sgd_momentum(0.9),
                         workers=2, model_size=1, compression=config,
                         strategy="gtopk")
    with pytest.raises(TypeError, match="CompressionConfig"):
        make_train_step(None, None, sgd_momentum(0.9), lambda s: 0.1,
                        compression=config, ratio=0.2)


def test_train_factories_reject_unknown_legacy_kwargs():
    from repro.optim import sgd_momentum
    from repro.train import init_train_state, make_train_step

    with pytest.raises(TypeError, match="unexpected"):
        make_train_step(None, None, sgd_momentum(0.9), lambda s: 0.1,
                        compressor="topk", ratioo=0.1)
    with pytest.raises(TypeError, match="unexpected"):
        init_train_state({"w": jnp.ones((8,))}, sgd_momentum(0.9),
                         workers=2, model_size=1, compresor="topk")


def test_publisher_config_rejections():
    from repro.serve import publisher_config

    with pytest.raises(ValueError, match="sparse"):
        publisher_config(CompressionConfig(compressor="none"))
    with pytest.raises(ValueError, match="density_policy"):
        publisher_config(CompressionConfig(
            compressor="topk", ratio=0.1,
            density_policy=make_policy("variance")))
    with pytest.raises(ValueError, match="momentum"):
        publisher_config(CompressionConfig(
            compressor="topk", ratio=0.1, momentum_correction=0.5))
    c = CompressionConfig(compressor="topk", ratio=0.1)
    assert publisher_config(c) is c
    assert publisher_config(None) == CompressionConfig()


# ---------------------------------------------------------------------------
# signature pins
# ---------------------------------------------------------------------------


def _positional(fn):
    return [p.name for p in inspect.signature(fn).parameters.values()
            if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)]


def test_signature_pins():
    assert _positional(aggregate.aggregate_compressed) == \
        ["grads", "resid", "config"]
    assert _positional(aggregate.aggregate_bucketed) == \
        ["grads", "resid", "layout", "config"]
    assert _positional(aggregate.aggregate_bucketed_chunked) == \
        ["grads", "resid", "layout", "plan", "config"]
    for fn in (aggregate.aggregate_compressed,
               aggregate.aggregate_bucketed,
               aggregate.aggregate_bucketed_chunked):
        kw = inspect.signature(fn).parameters
        for name in ("resid2", "world", "adapt_state", "step"):
            assert kw[name].kind == kw[name].KEYWORD_ONLY, (fn, name)

    assert _positional(aggregate.aggregate_dense) == ["grads", "data_axes"]

    from repro.train import init_train_state, make_train_step
    for fn in (make_train_step, init_train_state):
        p = inspect.signature(fn).parameters
        assert p["compression"].kind == p["compression"].KEYWORD_ONLY
        assert p["compression"].default is None

    from repro.serve import publish
    assert _positional(publish) == ["state", "params", "layout", "config",
                                    "key"]
