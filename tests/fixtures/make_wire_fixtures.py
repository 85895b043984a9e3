"""Regenerate the compiled wire-stage HLO fixtures in this directory.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python tests/fixtures/make_wire_fixtures.py

One fixture per wire strategy: the bucketed aggregation wire stage
(encode -> strategy collectives -> mean) compiled for the strategy's
canonical test mesh, post-optimization HLO text, gzipped.  The meshes
and the layout geometry here are pinned — tests/test_hlo_cost.py
recomputes the expected collective bytes/messages from the same layout
closed forms, so changing anything here requires re-pinning those
tests.  Sidecar ``<name>.json`` records the geometry each dump was
built with.
"""
import gzip
import json
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.compression import CompressionConfig
from repro.core.compressors import get_compressor
from repro.dist.aggregate import aggregate_bucketed
from repro.dist.layout import build_layout
from repro.launch.mesh import make_mesh

HERE = os.path.dirname(os.path.abspath(__file__))

# pinned geometry (mirrored by tests/test_hlo_cost.py)
PARAMS = {"a": (40, 30), "b": (17,)}
MODEL_SIZE = 1
RATIO = 0.05
COMPRESSOR = "topk"

CASES = [
    ("allgather", (4, 2), ("data", "model")),
    ("gtopk", (4, 2), ("data", "model")),
    ("hierarchical", (2, 2, 2), ("pod", "data", "model")),
    ("hier_gtopk", (2, 2, 2), ("pod", "data", "model")),
]


def compile_wire(strategy, shape, axes_names):
    mesh = make_mesh(shape, axes_names)
    sizes = dict(zip(axes_names, shape))
    data_axes = tuple(a for a in axes_names if a != "model")
    world = 1
    for a in data_axes:
        world *= sizes[a]
    params = {k: jnp.zeros(s) for k, s in PARAMS.items()}
    spec = get_compressor(COMPRESSOR)
    layout = build_layout(params, MODEL_SIZE, RATIO, spec)
    cfg = CompressionConfig(compressor=COMPRESSOR, ratio=RATIO,
                            strategy=strategy, backend="reference")
    needs_r2 = strategy in ("hierarchical", "hier_gtopk")

    def body(g, e, *r2):
        out = aggregate_bucketed(
            g, e[0], layout, cfg, data_axes, "model",
            jax.random.PRNGKey(7), resid2=r2[0][0] if r2 else None,
            world=world)
        outs = (out.agg, out.resid[None])
        if r2:
            outs += (out.resid2[None],)
        return outs

    gspec = jax.tree.map(lambda _: P(data_axes), params)
    in_specs = (gspec, P(data_axes)) + ((P(data_axes),) if needs_r2 else ())
    out_specs = (jax.tree.map(lambda _: P(), params), P(data_axes)) + (
        (P(data_axes),) if needs_r2 else ())
    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs,
                               axis_names=set(axes_names), check_vma=False))
    D = layout.model_size * layout.d_row_total
    g = {k: jnp.zeros((world,) + s) for k, s in PARAMS.items()}
    e = jnp.zeros((world, D))
    args = (g, e) + ((jnp.zeros((world, D)),) if needs_r2 else ())
    return fn.lower(*args).compile().as_text(), layout, world, sizes


def main():
    for strategy, shape, axes_names in CASES:
        hlo, layout, world, sizes = compile_wire(strategy, shape, axes_names)
        name = f"wire_{strategy}_{'x'.join(map(str, shape))}"
        with gzip.open(os.path.join(HERE, name + ".hlo.gz"), "wt") as f:
            f.write(hlo)
        meta = {
            "strategy": strategy, "mesh": list(shape),
            "axes": list(axes_names), "world": world,
            "n_pods": sizes.get("pod", 1),
            "model_size": MODEL_SIZE, "ratio": RATIO,
            "compressor": COMPRESSOR,
            "params": {k: list(v) for k, v in PARAMS.items()},
            "k_cap_total": layout.k_cap_total,
            "pair_bits": layout.pair_bits(None),
        }
        with open(os.path.join(HERE, name + ".json"), "w") as f:
            json.dump(meta, f, indent=1)
            f.write("\n")
        print(f"wrote {name}.hlo.gz ({len(hlo)} chars)")


if __name__ == "__main__":
    main()
