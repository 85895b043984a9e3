"""Distributed layer: sharding rules, compressed gradient aggregation and
the compressed-gradient wire layout.

``sharding``   per-leaf PartitionSpec rules for the ``model`` axis plus the
               serve-time data-axis layouts (params, caches) and the
               TrainState specs entering the shard_map region.
``layout``     the static ``BucketLayout``: every leaf's padded rows and
               codec capacity packed into one flat bucket / one wire
               block with static segment offsets (DESIGN.md §10).
``aggregate``  paper Eq. (2) at scale: per-worker error-feedback
               compression, then one of three wire strategies over the
               data axes — flat sparse all-gather, two-level
               pod -> global reduction, or gTop-k recursive doubling
               (``STRATEGIES``; DESIGN.md §3-§4, §7) — dispatched either
               per leaf (``aggregate_compressed``) or as ONE collective
               per wire level per step (``aggregate_bucketed``).
"""
from repro.dist import aggregate, layout, sharding
from repro.dist.aggregate import (STRATEGIES, AggregateResult,
                                  aggregate_bucketed,
                                  aggregate_bucketed_chunked,
                                  aggregate_compressed, aggregate_dense,
                                  bucket_compress, gtopk_simulate,
                                  init_residuals, resolve_strategy,
                                  strategy_wire_pairs)
from repro.dist.layout import (BucketLayout, ChunkPlan, build_chunk_plan,
                               build_layout, chunk_view, collective_count,
                               init_flat_residual, leaf_key_salt,
                               pack_grads, pack_residual_arrays,
                               rebudget_layout, unpack_residual_arrays,
                               unpack_tree, validate_chunk_plan)
from repro.dist.sharding import (cache_specs, param_spec, param_specs,
                                 train_state_specs)

__all__ = [
    "aggregate", "layout", "sharding",
    "STRATEGIES", "AggregateResult", "aggregate_bucketed",
    "aggregate_bucketed_chunked",
    "aggregate_compressed", "aggregate_dense", "bucket_compress",
    "gtopk_simulate", "init_residuals", "resolve_strategy",
    "strategy_wire_pairs",
    "BucketLayout", "ChunkPlan", "build_chunk_plan", "build_layout",
    "chunk_view", "collective_count", "init_flat_residual",
    "leaf_key_salt", "pack_grads", "pack_residual_arrays",
    "rebudget_layout", "unpack_residual_arrays", "unpack_tree",
    "validate_chunk_plan",
    "cache_specs", "param_spec", "param_specs", "train_state_specs",
]
