"""The FLOPs functions: the decoder family's forward FLOPs against XLA's
cost analysis of the program's forward matmuls at a small size, and the
training FLOPs of the cells' configurations."""
import json

import jax
import jax.numpy as jnp

from bench import flops, reference, spec, system
from bench.families import decoder
from bench.tests import tiny


def _dot_flops(jaxpr, mult=1.0) -> float:
    """XLA's count of every dot_general in ``jaxpr``, each compiled on
    its own, times the trip count of the scans around it."""
    total = 0.0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            a, b = (v.aval for v in eqn.invars)
            dn = eqn.params["dimension_numbers"]
            cost = jax.jit(lambda x, y: jax.lax.dot_general(x, y, dn)).lower(
                jax.ShapeDtypeStruct(a.shape, a.dtype),
                jax.ShapeDtypeStruct(b.shape, b.dtype)).compile()
            total += mult * cost.cost_analysis()["flops"]
        inner = mult * eqn.params.get("length", 1)
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                total += _dot_flops(sub, inner)
    return total


def test_forward_flops_match_xla():
    model = tiny.DENSE
    from repro.models.model import forward

    cfg = system.model_config(model)
    B, S = 2, 32
    params = jax.eval_shape(
        lambda k: reference.init_params(decoder.param_shapes(model), k),
        jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, t: forward(p, cfg, tokens=t,
                                                remat=False))(params, tokens)
    want = _dot_flops(jaxpr.jaxpr)
    assert decoder.forward_flops_per_token(model, S) * B * S == want


def test_train_flops_of_the_cell():
    with open(spec.HERE / "configs" / "stablelm-2-1.6b-L3.json") as f:
        model = json.load(f)["model"]
    want = 4.574e12 / 2048      # the ahead-of-time compile's estimate
    got = flops.train_flops_per_token(decoder, model, 1024)
    assert abs(got - want) <= 1e-3 * want


def test_train_flops_of_the_cell_are_the_parents():
    """``mfu``'s FLOPs per step: the count the harness made before the
    families, to the last unit."""
    cell = spec.load("stablelm_efjnp_1chip")
    assert flops.train_flops_per_token(
        cell["family"], cell["model"], cell["job"]["seq"]) == 2233466880.0
