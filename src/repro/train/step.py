"""Train-step factory: wires the model loss, the compressed gradient
aggregation (paper Eq. 2) and the optimizer into one jitted step.

Structure (DESIGN.md §4):

  jax.jit
   └─ jax.shard_map        manual over ("pod","data"), AUTO over "model"
       ├─ jax.value_and_grad(loss)   per-worker grads on the local batch;
       │                             params/activations GSPMD-sharded
       │                             over "model" transparently
       ├─ aggregate_compressed       local per-shard selection + the
       │                             chosen wire strategy over the data
       │                             axes: sparse all_gather, gTop-k
       │                             ppermute rounds, or two-level pod
       │                             reduction (lax.pmean for Dense-SGD)
       └─ optimizer.update           identical on every worker
"""
from __future__ import annotations

import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.compression import CompressionConfig, as_config
from repro.dist import aggregate
from repro.dist.layout import build_chunk_plan
from repro.dist.sharding import batch_specs, param_spec, train_state_specs
from repro.launch.mesh import data_axes_of, data_world_size, model_axis_size
from repro.models import loss_fn as model_loss_fn
from repro.optim import Optimizer


def constrain_params(params, model_axis: str, msize: int):
    """Pin the model-axis sharding of every param leaf inside the
    partial-manual region — input shardings on auto axes do not survive
    the shard_map boundary, and without this the whole model computes
    replicated over ``model``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: jax.lax.with_sharding_constraint(
            leaf, param_spec(path, leaf, model_axis, msize)),
        params)


def _joint(data_axes):
    return data_axes if len(data_axes) > 1 else data_axes[0]


def worker_index(data_axes):
    idx = jnp.int32(0)
    for a in data_axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def _chunk_grad_seam(groups):
    """custom-vjp identity over the flat param-leaf tuple whose BACKWARD
    wraps each chunk group's cotangents in one ``optimization_barrier``
    (DESIGN.md §11).

    The forward is a no-op, so loss values and gradients are bit-exact.
    The barriers make the chunk structure explicit in the backward
    jaxpr: every group's grads become available as one unit with no data
    edge to any other group's cotangents, which is the boundary
    ``aggregate_bucketed_chunked`` overlaps against — chunk c's compress
    + collective can be scheduled as soon as chunk c's barrier resolves,
    while chunk c+1's backward is still in flight.  One barrier per
    group, countable via ``launch.hlo_cost.count_schedule_markers``."""
    @jax.custom_vjp
    def seam(leaves):
        return leaves

    def fwd(leaves):
        return leaves, None

    def bwd(_, cts):
        out = list(cts)
        for g in groups:
            block = jax.lax.optimization_barrier(
                tuple(out[g.seg_lo:g.seg_hi]))
            out[g.seg_lo:g.seg_hi] = list(block)
        return (tuple(out),)

    seam.defvjp(fwd, bwd)
    return seam


# Legacy make_train_step kwargs the deprecation shim still accepts; each
# maps onto one CompressionConfig field (hierarchical via resolve_strategy).
_LEGACY_STEP_KEYS = ("compressor", "ratio", "strategy", "hierarchical",
                     "codec_dtype", "momentum_correction", "backend",
                     "density_policy", "chunks")


def _step_config_from_legacy(legacy: dict) -> CompressionConfig:
    unknown = set(legacy) - set(_LEGACY_STEP_KEYS)
    if unknown:
        raise TypeError("make_train_step got unexpected kwargs "
                        f"{sorted(unknown)}")
    warnings.warn(
        "make_train_step: loose compression kwargs "
        f"({sorted(legacy)}) are deprecated; pass "
        "compression=core.compression.CompressionConfig(...) instead",
        DeprecationWarning, stacklevel=3)
    return CompressionConfig(
        compressor=legacy.get("compressor", "gaussiank"),
        ratio=legacy.get("ratio", 0.001),
        strategy=aggregate.resolve_strategy(
            legacy.get("strategy", "allgather"),
            legacy.get("hierarchical", False)),
        codec_dtype=legacy.get("codec_dtype"),
        momentum_correction=legacy.get("momentum_correction", 0.0),
        backend=legacy.get("backend", "auto"),
        density_policy=legacy.get("density_policy"),
        chunks=legacy.get("chunks", 1))


def make_train_step(cfg, mesh, optimizer: Optimizer, lr_fn: Callable,
                    *, compression: Optional[CompressionConfig] = None,
                    remat: bool = True, seed: int = 0,
                    loss_fn: Optional[Callable] = None,
                    layout=None, **legacy):
    """Returns ``step_fn(state, batch) -> (state, metrics)``, already
    jit+shard_map wrapped for ``mesh``.

    ``compression`` (a ``core.compression.CompressionConfig``) is the one
    value describing what to compress with and how to move it: compressor
    name (``"none"`` gives the Dense-SGD baseline), density ratio, wire
    strategy, codec dtype, DGC momentum correction, EF backend, adaptive
    ``DensityPolicy`` (DESIGN.md §9) and chunk count.  ``None`` means the
    default config.  The pre-config loose kwargs (``compressor=``,
    ``ratio=``, ``strategy=``, ``hierarchical=``, ...) still work but
    forward through a ``DeprecationWarning`` shim.

    ``layout`` (a ``dist/layout.BucketLayout`` built from the SAME
    params + compression configuration) dispatches the aggregation
    through the flat bucketed pipeline (``aggregate_bucketed``,
    DESIGN.md §10): the state's residuals are the flat buffers of
    ``init_train_state(..., layout=...)`` and every wire level is one
    collective per step instead of one per leaf.  ``layout=None`` keeps
    the per-leaf loop (bit-identical results).

    ``compression.chunks > 1`` (with a ``layout``) switches to the
    chunked overlapped schedule (DESIGN.md §11): the bucket is split
    into N leaf-aligned chunk groups, a custom-vjp seam releases each
    group's gradients as one unit during the backward pass, and
    ``aggregate_bucketed_chunked`` issues one compress+collective chain
    per group — bit-identical results, N collectives per wire level.
    The TrainState is chunk-count independent (the flat residual layout
    never changes), so checkpoints move freely across chunk settings."""
    if legacy:
        if compression is not None:
            raise TypeError(
                "make_train_step: legacy kwargs "
                f"{sorted(legacy)} cannot be combined with a "
                "CompressionConfig — fold them in via "
                "compression.replace(...)")
        compression = _step_config_from_legacy(legacy)
    compression = as_config(compression)
    data_axes = data_axes_of(mesh)
    joint = _joint(data_axes)
    msize = model_axis_size(mesh)
    dense = compression.dense
    spec = compression.spec
    density_policy = compression.density_policy
    if layout is not None and not dense:
        # fail at factory time, not deep inside the traced step
        if layout.model_size != msize:
            raise ValueError(f"layout model_size={layout.model_size} != "
                             f"mesh model axis {msize}")
        if layout.spec_name != spec.name:
            raise ValueError(f"layout compressor {layout.spec_name!r} != "
                             f"{spec.name!r}")
        if abs(layout.ratio - float(compression.ratio)) > 1e-12:
            raise ValueError(
                f"layout ratio {layout.ratio} != {compression.ratio}")
        if layout.adaptive != compression.adaptive:
            raise ValueError("layout density mode does not match "
                             "density_policy; rebuild the layout")
    chunk_plan = None
    if compression.chunks > 1:
        if dense or layout is None:
            raise ValueError(
                "chunks > 1 needs the bucketed sparse pipeline: pass "
                "layout= (the chunked schedule re-dispatches the flat "
                "wire block; the per-leaf and Dense-SGD paths have no "
                "bucket to chunk)")
        chunk_plan = build_chunk_plan(layout, compression.chunks)
    seam = (_chunk_grad_seam(chunk_plan.groups)
            if chunk_plan is not None else None)
    base_key = jax.random.PRNGKey(seed)
    constrain = lambda tree: constrain_params(tree, "model", msize)  # noqa: E731
    loss = loss_fn or (lambda p, b: model_loss_fn(p, cfg, b, remat=remat,
                                                  constrain=constrain))

    def per_worker_step(state, batch):
        if (density_policy is not None and density_policy.ema > 0.0
                and "adaptk" not in state):
            raise ValueError(
                "density_policy.ema > 0 needs the controller state; "
                "allocate it via init_train_state(..., "
                "density_policy=...) — without it the EMA would be "
                "silently disabled")
        params = constrain_params(state["params"], "model", msize)

        def grad_loss(p, b):
            if seam is not None:
                # route params through the chunk seam so the backward
                # pass hands each chunk group's cotangents over as one
                # unit
                leaves, ptd = jax.tree_util.tree_flatten(p)
                p = jax.tree_util.tree_unflatten(
                    ptd, list(seam(tuple(leaves))))
            # forward ops read ".../jvp(model)/...", backward ones
            # ".../transpose(jvp(model))/..." (DESIGN.md §16)
            with jax.named_scope("model"):
                return loss(p, b)

        (l, metrics), grads = jax.value_and_grad(grad_loss, has_aux=True)(
            params, batch)
        grads = constrain_params(grads, "model", msize)

        if dense:
            agg = aggregate.aggregate_dense(grads, data_axes)
            new_resid = state.get("resid")
            new_resid2 = state.get("resid2")
            new_adapt = state.get("adaptk")
            agg_metrics = {}
        else:
            resid = jax.tree.map(lambda e: e[0], state["resid"])
            resid2 = (jax.tree.map(lambda e: e[0], state["resid2"])
                      if "resid2" in state else None)
            key = jax.random.fold_in(base_key, state["step"])
            key = jax.random.fold_in(key, worker_index(data_axes))
            # runtime-state kwargs shared by all dispatch granularities —
            # everything *configuration* already rides in ``compression``
            agg_kw = dict(resid2=resid2, world=data_world_size(mesh),
                          adapt_state=state.get("adaptk"),
                          step=state["step"])
            if chunk_plan is not None:
                res = aggregate.aggregate_bucketed_chunked(
                    grads, resid, layout, chunk_plan, compression,
                    data_axes, "model", key, **agg_kw)
            elif layout is not None:
                res = aggregate.aggregate_bucketed(
                    grads, resid, layout, compression, data_axes, "model",
                    key, **agg_kw)
            else:
                res = aggregate.aggregate_compressed(
                    grads, resid, compression, data_axes, "model",
                    msize, key, **agg_kw)
            agg, nr, nr2 = res.agg, res.resid, res.resid2
            new_adapt, agg_metrics = res.adapt_state, res.metrics
            new_resid = jax.tree.map(lambda e: e[None], nr)
            new_resid2 = (jax.tree.map(lambda e: e[None], nr2)
                          if "resid2" in state else None)

        lr = lr_fn(state["step"])
        agg = constrain_params(agg, "model", msize)
        with jax.named_scope("optimizer"):
            new_params, new_opt = optimizer.update(params, state["opt"], agg,
                                                   lr)
        new_params = constrain_params(new_params, "model", msize)
        new_state = dict(state, params=new_params, opt=new_opt,
                         step=state["step"] + 1)
        if new_resid is not None and "resid" in state:
            new_state["resid"] = new_resid
        if new_resid2 is not None and "resid2" in state:
            new_state["resid2"] = new_resid2
        if new_adapt is not None and "adaptk" in state:
            new_state["adaptk"] = new_adapt

        metrics = {k: jax.lax.pmean(v, joint) for k, v in metrics.items()}
        metrics["lr"] = lr
        metrics.update(agg_metrics)
        return new_state, metrics

    @jax.jit
    def step_fn(state, batch):
        sm = jax.shard_map(
            per_worker_step, mesh=mesh,
            in_specs=(train_state_specs(state, joint),
                      batch_specs(batch, joint)),
            out_specs=(train_state_specs(state, joint), P()),
            axis_names=set(data_axes), check_vma=False)
        return sm(state, batch)

    return step_fn


def required_workers(mesh) -> int:
    return data_world_size(mesh)
