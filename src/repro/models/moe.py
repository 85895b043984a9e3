"""Mixture-of-Experts FFN that holds a share of the experts and drops no
token (DESIGN.md §17).

Routing is over all ``num_experts``: softmax scores, greedy top-k, the
gates renormalized only under ``norm_topk_prob`` and scaled by
``routed_scaling_factor``.  The layer holds experts ``[expert_offset,
expert_offset + held)`` (all of them by default), as one chip of an
expert-parallel deployment holds its share.

Dispatch sorts the ``N * K`` assignments by expert; the expert products
are the grouped matmul of megablox (``kernels/grouped.py``: ``gmm``
forward, ``gmm`` and ``tgmm`` backward), which visits only the held
experts' rows, so rows routed elsewhere are never multiplied.  The static row count is the
dropless worst case ``N * K``, padded to the kernel's row tile.  Rows no
held expert covers are masked (``jnp.where``) on the way in and out.
Dispatch and combine are permutations, each a gather whose VJP is the
gather by the inverse order; the K choices are summed by a reshape, so
the layer has no scatter in either pass.  An optional per-expert
capacity (``capacity_factor``) masks the sorted rows beyond it.

Includes shared experts (DeepSeek-MoE), run once on every token, and a
load-balance auxiliary loss (``router_aux``).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.grouped import ROW_TILE, grouped_matmul
from repro.models.config import ModelConfig
from repro.models.layers import _dense_init, init_mlp, mlp
from repro.spmd import auto_axes, manual_over_auto_axes


def init_moe(key, cfg: ModelConfig, dtype):
    D, F, E = cfg.d_model, cfg.expert_d_ff(), cfg.num_experts
    G = cfg.held_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": _dense_init(ks[0], (D, E), dtype),
        "w_gate": _dense_init(ks[1], (G, D, F), dtype),
        "w_up": _dense_init(ks[2], (G, D, F), dtype),
        "w_down": _dense_init(ks[3], (G, F, D), dtype),
    }
    if cfg.num_shared_experts:
        p["shared"] = init_mlp(ks[4], D, F * cfg.num_shared_experts, dtype)
    return p


def capacity(n_tokens: int, cfg: ModelConfig) -> int:
    c = math.ceil(n_tokens * cfg.experts_per_token /
                  max(cfg.num_experts, 1) * cfg.capacity_factor)
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU tiling


# ---------------------------------------------------------------------------
# the permutations and the expert products
# ---------------------------------------------------------------------------


@jax.custom_vjp
def permute(x, order, inverse):
    """``x[order]`` for a permutation ``order`` whose inverse is
    ``inverse``; the VJP is the gather ``g[inverse]``, not a scatter."""
    return x[order]


def _permute_fwd(x, order, inverse):
    return x[order], (order, inverse)


def _permute_bwd(res, g):
    order, inverse = res
    return g[inverse], None, None


permute.defvjp(_permute_fwd, _permute_bwd)


def _experts(rows, w_gate, w_up, w_down, group_sizes, start):
    """The held experts' SwiGLU on their sorted rows."""
    h = jax.nn.silu(grouped_matmul(rows, w_gate, group_sizes, start))
    h = h * grouped_matmul(rows, w_up, group_sizes, start)
    return grouped_matmul(h, w_down, group_sizes, start)


def _expert_products(rows, w_gate, w_up, w_down, group_sizes, start):
    """``_experts`` in the kernel's manual region.  Where the ``model``
    axis is automatic and splits the expert width F, the weights keep
    ``dist/sharding``'s Megatron split: each device multiplies its
    columns of ``w_gate`` / ``w_up`` and its rows of ``w_down``, and the
    partial sums are added over ``model``.  Otherwise the operands are
    replicated."""
    n = auto_axes().get("model", 1)
    if n == 1 or w_gate.shape[-1] % n:
        return manual_over_auto_axes(_experts, rows, w_gate, w_up, w_down,
                                     group_sizes, start)

    def split(rows, w_gate, w_up, w_down, group_sizes, start):
        return jax.lax.psum(
            _experts(rows, w_gate, w_up, w_down, group_sizes, start),
            "model")

    col, row = P(None, None, "model"), P(None, "model", None)
    return manual_over_auto_axes(split, rows, w_gate, w_up, w_down,
                                 group_sizes, start,
                                 in_specs=(P(), col, col, row, P(), P()),
                                 out_specs=P())


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------


def _aux_loss(probs, eidx, cfg: ModelConfig, B: int, T: int):
    E, K = cfg.num_experts, cfg.experts_per_token
    if cfg.router_aux == "seq":
        # per sequence: f_e = E / (K T) * its assignments to e, P_e its
        # mean score of e
        counts = jnp.sum(jax.nn.one_hot(eidx.reshape(B, T * K), E), 1)
        f = counts * (E / (K * T))
        P = jnp.mean(probs.reshape(B, T, E), 1)
        return cfg.router_aux_coef * jnp.mean(jnp.sum(f * P, -1))
    me = jnp.mean(probs, axis=0)                             # (E,)
    ce = jnp.mean(jax.nn.one_hot(eidx[:, 0], E), axis=0)
    return cfg.router_aux_coef * E * jnp.sum(me * ce)


def moe_layer(p, x, cfg: ModelConfig):
    """x: (B, T, D) -> (out, aux_loss, counters): ``moe_rows_held``, the
    assignments routed to held experts; ``moe_load_max``, the largest
    held expert's rows over a balanced expert's ``N K / E``;
    ``moe_rows_dropped``, held rows the capacity cap masked (0 without
    one)."""
    B, T, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    G, off = cfg.held_experts, cfg.expert_offset
    N = B * T
    M = N * K
    Mp = -(-M // ROW_TILE) * ROW_TILE
    xt = x.reshape(N, D)

    with jax.named_scope("moe.route"):
        logits = (xt @ p["router"]).astype(jnp.float32)      # (N, E)
        probs = jax.nn.softmax(logits, axis=-1)
        _, eidx = jax.lax.top_k(jax.lax.stop_gradient(probs), K)  # (N, K)
        # the chosen scores by a one-hot product, whose VJP is no scatter
        # (top_k's is)
        gate = jnp.sum(jax.nn.one_hot(eidx, E, dtype=probs.dtype)
                       * probs[:, None, :], -1)
        if cfg.norm_topk_prob:
            gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)
        if cfg.routed_scaling_factor != 1.0:
            gate = gate * cfg.routed_scaling_factor
        aux = _aux_loss(probs, eidx, cfg, B, T)

        # sort the assignments by expert (token-major within one)
        flat_e = eidx.reshape(-1).astype(jnp.int32)          # (M,)
        order = jnp.argsort(flat_e, stable=True)
        inverse = jnp.argsort(order)
        sorted_e = flat_e[order]
        bounds = jnp.searchsorted(sorted_e, jnp.arange(E + 1),
                                  side="left").astype(jnp.int32)
        group_sizes = bounds[1:] - bounds[:-1]               # (E,)
        held = (sorted_e >= off) & (sorted_e < off + G)
        keep = held
        if cfg.capacity_factor is not None:
            rank = jnp.arange(M) - bounds[sorted_e]          # within expert
            keep = held & (rank < capacity(N, cfg))
        stats = {
            "moe_rows_held": jnp.sum(held).astype(jnp.float32),
            "moe_load_max": jnp.max(group_sizes[off:off + G]).astype(
                jnp.float32) / (M / E),
            "moe_rows_dropped": jnp.sum(held & ~keep).astype(jnp.float32),
        }
        keep = jnp.pad(keep, (0, Mp - M))[:, None]

    with jax.named_scope("moe.dispatch"):
        # each token's K copies, token-major, then sorted by expert
        copies = jnp.broadcast_to(xt[:, None], (N, K, D)).reshape(M, D)
        rows = jnp.pad(permute(copies, order, inverse), ((0, Mp - M), (0, 0)))
        rows = jnp.where(keep, rows, 0)

    with jax.named_scope("moe.experts"):
        wdt = jnp.dtype(cfg.expert_operand_dtype)
        w = [p[k].astype(wdt) for k in ("w_gate", "w_up", "w_down")]
        y = _expert_products(rows, *w, group_sizes,
                             jnp.asarray(off, jnp.int32))
        y = jnp.where(keep, y, 0)

    with jax.named_scope("moe.combine"):
        y = permute(y[:M], inverse, order).reshape(N, K, D)
        out = jnp.sum(y * gate[..., None].astype(y.dtype), axis=1)

    if cfg.num_shared_experts:
        out = out + mlp(p["shared"], xt)
    return out.reshape(B, T, D).astype(x.dtype), aux, stats


def moe_ffn(p, x, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """x: (B, T, D) -> (out, aux_loss)."""
    out, aux, _ = moe_layer(p, x, cfg)
    return out, aux
