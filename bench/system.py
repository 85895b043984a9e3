"""The system under test: the program's compressed training step, built
the way ``repro/launch/train.py`` builds it, with the weights made by
the benchmark (``reference.init_params``) from the seed, over the
program's own tree of parameters.

This module is the one place that imports the program.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from bench import reference

def model_config(model: dict):
    """The program's ``ModelConfig`` of a configuration's ``model``: every
    key it sets is passed.  Raises KeyError for a key that is not a
    field of ``ModelConfig``."""
    from repro.models.config import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    unknown = sorted(set(model) - fields)
    if unknown:
        raise KeyError(f"{model.get('name')!r}: ModelConfig has no field "
                       f"{unknown}")
    kw = dict(model)
    for k in ("block_pattern", "ffn_pattern"):
        if k in kw:
            kw[k] = tuple(kw[k])
    return ModelConfig(**kw).validate()


class System:
    """One cell's step, state factory and feed.

    ``fault`` plants a fault in the timed path, for the benchmark's own
    tests: ``"unchanged"`` (the step returns its state), ``"half_batch"``
    (the loss over the first half of each worker's rows only),
    ``"answer"`` (the update of the lm_head leaf doubled where the step
    produces it); and ``"fused"``, the program's fused error-feedback
    kernels in place of the job's backend, whose per-block staging
    truncates a clustered selection (``PERF.md``, Open questions).
    """

    def __init__(self, model: dict, job: dict, seed: int, fault=None):
        from repro.core.compression import CompressionConfig
        from repro.data import batch_for
        from repro.dist.layout import build_layout
        from repro.dist.sharding import train_state_specs
        from repro.launch.mesh import make_mesh
        from repro.models import init_params as model_init
        from repro.models import loss_fn as model_loss
        from repro.optim import constant, sgd_momentum
        from repro.train import init_train_state, make_train_step

        self.model, self.job, self.seed = model, job, seed
        W, M = job["workers"], job["model_size"]
        self.mesh = make_mesh((W, M), ("data", "model"))
        self.devices = list(self.mesh.devices.flat)
        cfg = model_config(model)
        opt = sgd_momentum(job["momentum"])
        comp = CompressionConfig(
            compressor=job["compressor"], ratio=job["ratio"],
            strategy=job["strategy"],
            backend="fused" if fault == "fused" else job["backend"])
        key = jax.random.PRNGKey(seed)
        shapes = jax.tree.map(lambda s: tuple(s.shape), jax.eval_shape(
            partial(model_init, cfg), key))
        init = partial(reference.init_params, shapes)
        # 1. the bucket layout, from the parameters' shapes
        self.layout = build_layout(jax.eval_shape(init, key), M, comp)

        # 2-3. the state, made on the device with the step's shardings
        def make_state(key):
            params = init(key)
            return init_train_state(params, opt, workers=W, model_size=M,
                                    compression=comp, layout=self.layout)

        specs = train_state_specs(jax.eval_shape(make_state, key), "data")
        shard = jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs)
        self._key = key
        self._make_state = jax.jit(make_state, out_shardings=shard)
        self._make_params = jax.jit(
            init, out_shardings=NamedSharding(self.mesh, P()))

        # 4. the step
        loss_fn = None
        if fault == "half_batch":
            def loss_fn(p, b):
                half = {k: v[: v.shape[0] // 2] for k, v in b.items()}
                return model_loss(p, cfg, half, remat=job["remat"])
        step = inner = make_train_step(
            cfg, self.mesh, opt, constant(job["lr"]), compression=comp,
            layout=self.layout, remat=job["remat"], seed=seed,
            loss_fn=loss_fn)
        if fault == "unchanged":
            step = jax.jit(lambda s, b: (s, inner(s, b)[1]))
        elif fault == "answer":
            def altered(s, b):
                new, m = inner(s, b)
                p0, p1 = s["params"]["lm_head"], new["params"]["lm_head"]
                params = dict(new["params"], lm_head=2 * p1 - p0)
                return dict(new, params=params), m
            step = jax.jit(altered)
        elif fault not in (None, "half_batch", "fused"):
            raise ValueError(f"no such planted fault {fault!r}")
        self.step = step

        # 5. the feed: the program's own batches, one compiled program
        # for every step number and seed
        B, S = job["batch_per_worker"] * W, job["seq"]
        self.feed = jax.jit(lambda i, s: batch_for(
            cfg, i, global_batch=B, seq_len=S, seed=s))
        self._seed_arg = jnp.uint32(seed)
        self.tokens_per_step = B * S

    def new_state(self):
        return self._make_state(self._key)

    def batch(self, i: int):
        return self.feed(jnp.uint32(i), self._seed_arg)

    def initial_params(self):
        return self._make_params(self._key)


@jax.jit
def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def leaf_counts(tree):
    return jnp.stack([jnp.count_nonzero(x) for x in jax.tree.leaves(tree)])


@jax.jit
def change_norms(a, b):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])
