"""The reference against the program at a small size on the CPU, and its
weights in the program's layout at the cells' real sizes (shapes only)."""
import json
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, spec, system
from bench.families import decoder
from bench.tests import moe_family, tiny


@pytest.mark.parametrize("name", ["stablelm-2-1.6b-L3"])
def test_weights_in_program_layout(name):
    from repro.models import init_params

    with open(spec.HERE / "configs" / f"{name}.json") as f:
        conf = json.load(f)
    model = conf["model"]
    key = jax.random.PRNGKey(0)
    ours = jax.eval_shape(
        lambda k: reference.init_params(
            spec.family(conf["family"]).param_shapes(model), k), key)
    theirs = jax.eval_shape(
        lambda k: init_params(system.model_config(model), k), key)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert [x.shape for x in jax.tree.leaves(ours)] == \
        [x.shape for x in jax.tree.leaves(theirs)]
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(ours))
    assert n == conf["parameters"]


def test_loss_and_grad_match_program():
    _loss_and_grad_match_program(decoder, tiny.DENSE)


def test_moe_family_loss_and_grad_match_program():
    """The test-only MoE family against the program's MoE FFN, with its
    shared expert and load-balance term."""
    _loss_and_grad_match_program(moe_family, tiny.MOE)


def _loss_and_grad_match_program(family, model):
    from repro.data import batch_for
    from repro.models import loss_fn

    cfg = system.model_config(model)
    params = jax.jit(partial(reference.init_params,
                             family.param_shapes(model)))(
        jax.random.PRNGKey(3))
    batch = batch_for(cfg, 0, global_batch=2, seq_len=32, seed=5)
    ref_batch = reference.lm_batch(0, global_batch=2, seq_len=32,
                                   vocab=model["vocab_size"], seed=5)
    np.testing.assert_array_equal(batch["tokens"], ref_batch["tokens"])
    np.testing.assert_array_equal(batch["labels"], ref_batch["labels"])
    with jax.default_matmul_precision("highest"):
        lr, gr = jax.value_and_grad(family.loss)(
            params, ref_batch["tokens"], ref_batch["labels"], model)
        (lp, _), gp = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, batch, remat=False), has_aux=True)(
            params)
    assert abs(float(lr) - float(lp)) <= 1e-5 * abs(float(lr))
    for a, b in zip(jax.tree.leaves(gr), jax.tree.leaves(gp)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * scale


def test_gaussiank_matches_program_reference_path():
    from repro.core.compressors import get_compressor

    u = jax.random.normal(jax.random.PRNGKey(1), (20000,), jnp.float32)
    k, k_cap = reference.gaussiank_budget(u.size, 0.01)
    spec_ = get_compressor("gaussiank")
    values, indices = spec_.select(u, k, None)
    assert spec_.k_cap(k, u.size) == k_cap
    want = jnp.zeros_like(u).at[jnp.where(indices >= 0, indices, u.size)].set(
        values, mode="drop")
    sel, new_e = reference.gaussiank_ef(u, k, k_cap)
    np.testing.assert_array_equal(np.asarray(sel), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(sel + new_e), np.asarray(u))
