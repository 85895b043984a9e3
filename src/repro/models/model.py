"""Unified decoder model covering all assigned architecture families.

A model is a cycled ``block_pattern`` (attn / swa / mla / mamba / slstm /
mlstm) crossed with a cycled ``ffn_pattern`` (mlp / moe / none), after
``first_dense_layers`` leading layers of ffn ``mlp`` (``params["lead"]``,
unrolled).  The patterned layers are grouped into ``reps`` repetitions of
the pattern period and executed under ``jax.lax.scan`` with
period-position-stacked parameters (compile time stays O(period), not
O(num_layers)); the remainder's tail runs unrolled.

Three entry points per model:
  ``loss_fn``      training forward + cross-entropy (+ MoE aux loss)
  ``prefill``      build the serve cache from a prompt (tokens or embeds)
  ``decode_step``  one token with a KV/SSM/recurrent cache

Caches are pytrees mirroring the layer grouping, so decode scans over the
same stacked structure.  Sliding-window layers keep a ring-buffer cache of
``sliding_window`` entries — decode HBM traffic for them is O(window), which
is what makes gemma3-style 5:1 local:global viable at 500k context.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec

from repro.models import layers as L
from repro.models import mla as A
from repro.models import moe as M
from repro.models import ssm as S
from repro.models import xlstm as X
from repro.models.config import ModelConfig

# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_block(key, cfg: ModelConfig, kind: str, ffn: str, dtype):
    kb, kf = jax.random.split(key)
    p: Dict[str, Any] = {"norm1": L.init_rmsnorm(cfg.d_model, dtype)}
    if kind in ("attn", "swa"):
        p["core"] = L.init_attention(kb, cfg, dtype)
    elif kind == "mla":
        p["core"] = A.init_mla(kb, cfg, dtype)
    elif kind == "mamba":
        p["core"] = S.init_mamba(kb, cfg, dtype)
    elif kind == "mlstm":
        p["core"] = X.init_mlstm(kb, cfg, dtype)
    elif kind == "slstm":
        p["core"] = X.init_slstm(kb, cfg, dtype)
    else:
        raise ValueError(kind)
    if ffn == "mlp":
        p["norm2"] = L.init_rmsnorm(cfg.d_model, dtype)
        p["ffn"] = L.init_mlp(kf, cfg.d_model, cfg.d_ff, dtype)
    elif ffn == "moe":
        p["norm2"] = L.init_rmsnorm(cfg.d_model, dtype)
        p["ffn"] = M.init_moe(kf, cfg, dtype)
    return p


def _lead_sig(cfg: ModelConfig, i: int):
    """(block kind, ffn kind) of leading dense layer ``i``."""
    return cfg.block_kind(i), "mlp"


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    cfg.validate()
    dtype = jnp.dtype(cfg.param_dtype)
    period = cfg.pattern_period
    lead = cfg.first_dense_layers
    reps, tail = divmod(cfg.num_layers - lead, period)
    k_embed, k_head, k_layers = jax.random.split(key, 3)

    params: Dict[str, Any] = {
        "embed": L._dense_init(k_embed, (cfg.vocab_size, cfg.d_model), dtype,
                               scale=1.0),
        "final_norm": L.init_rmsnorm(cfg.d_model, dtype),
        "lm_head": L._dense_init(k_head, (cfg.d_model, cfg.vocab_size), dtype),
    }
    lkeys = jax.random.split(k_layers, cfg.num_layers)
    if lead:
        params["lead"] = [_init_block(lkeys[i], cfg, *_lead_sig(cfg, i),
                                      dtype) for i in range(lead)]
    lkeys = lkeys[lead:]
    stack = []
    for pos in range(period if reps else 0):
        kind, ffn = cfg.layer_sig(pos)
        keys = jnp.stack([lkeys[r * period + pos] for r in range(reps)])
        stack.append(jax.vmap(
            lambda k: _init_block(k, cfg, kind, ffn, dtype))(keys))
    params["stack"] = stack
    params["tail"] = [
        _init_block(lkeys[reps * period + i], cfg,
                    *cfg.layer_sig(reps * period + i), dtype)
        for i in range(tail)
    ]
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# block application (training / prefill: full sequence)
# ---------------------------------------------------------------------------


def _apply_core(p, h, cfg: ModelConfig, kind: str):
    """Full-sequence core. Returns (out, cache_contrib) where cache_contrib
    becomes this layer's serve cache when prefilling."""
    if kind in ("attn", "swa"):
        window = cfg.sliding_window if kind == "swa" else 0
        out, (k, v) = L.attention(p, h, cfg, window=window)
        return out, ("kv", k, v)
    if kind == "mla":
        return A.mla(p, h, cfg), None
    if kind == "mamba":
        out, ssm_state, conv_tail = S.mamba_forward(p, h, cfg)
        return out, ("mamba", ssm_state, conv_tail)
    if kind == "mlstm":
        out, state = X.mlstm_forward(p, h, cfg)
        return out, ("mlstm", state)
    if kind == "slstm":
        out, state = X.slstm_forward(p, h, cfg)
        return out, ("slstm", state)
    raise ValueError(kind)


def _apply_block(p, h, cfg: ModelConfig, kind: str, ffn: str):
    """Returns (h, side, cache_contrib): ``side`` holds the layer's
    ``aux`` loss and, for an expert layer, its counters
    (``moe.moe_layer``)."""
    side = {"aux": jnp.zeros((), jnp.float32)}
    normed = L.rmsnorm(p["norm1"], h)
    core_out, cache = _apply_core(p["core"], normed, cfg, kind)
    if cfg.parallel_block and ffn != "none":
        f_out = L.mlp(p["ffn"], normed) if ffn == "mlp" else None
        if ffn == "moe":
            f_out, side["aux"], stats = M.moe_layer(p["ffn"], normed, cfg)
            side.update(stats)
        h = h + core_out + f_out
        return h, side, cache
    h = h + core_out
    if ffn == "mlp":
        h = h + L.mlp(p["ffn"], L.rmsnorm(p["norm2"], h))
    elif ffn == "moe":
        f_out, side["aux"], stats = M.moe_layer(
            p["ffn"], L.rmsnorm(p["norm2"], h), cfg)
        side.update(stats)
        h = h + f_out
    return h, side, cache


def _merge(a: dict, b: dict) -> dict:
    """Two layers' side outputs as one: sums, and the larger
    ``moe_load_max``."""
    out = dict(a)
    for k, v in b.items():
        if k not in out:
            out[k] = v
        elif k == "moe_load_max":
            out[k] = jnp.maximum(out[k], v)
        else:
            out[k] = out[k] + v
    return out


def _over_reps(side: dict) -> dict:
    """The scan's stacked side outputs reduced over the repetitions."""
    return {k: jnp.max(v) if k == "moe_load_max" else jnp.sum(v)
            for k, v in side.items()}


# ---------------------------------------------------------------------------
# training forward
# ---------------------------------------------------------------------------


def _forward(params, cfg: ModelConfig, tokens=None, embeds=None,
             remat: bool = True, constrain=None):
    """Full-sequence forward -> (logits, side), ``side`` the layers'
    merged side outputs (``_apply_block``)."""
    adt = jnp.dtype(cfg.activation_dtype)
    if embeds is not None:
        h = embeds.astype(adt)
    else:
        h = params["embed"][tokens].astype(adt)
    period = cfg.pattern_period
    reps = (cfg.num_layers - cfg.first_dense_layers) // period
    side = {"aux": jnp.zeros((), jnp.float32)}

    for i, p in enumerate(params.get("lead", [])):
        if constrain is not None:
            p = constrain(p)
        block = partial(_apply_block, cfg=cfg, kind=_lead_sig(cfg, i)[0],
                        ffn="mlp")
        if remat:
            block = jax.checkpoint(block)
        h, s, _ = block(p, h)
        side = _merge(side, s)

    def period_body(h, p_rep):
        if constrain is not None:
            p_rep = constrain(p_rep)
        side = {"aux": jnp.zeros((), jnp.float32)}
        for pos in range(period):
            kind, ffn = cfg.layer_sig(pos)
            h, s, _ = _apply_block(p_rep[pos], h, cfg, kind, ffn)
            side = _merge(side, s)
        if cfg.shard_activations:
            # §Perf knob: store the layer-boundary carry model-sharded
            h = jax.lax.with_sharding_constraint(
                h, PartitionSpec(None, None, "model"))
        return h, side

    if reps:
        body = jax.checkpoint(period_body) if remat else period_body

        def scan_body(h, p_rep):
            return body(h, p_rep)

        h, sides = jax.lax.scan(scan_body, h, params["stack"])
        side = _merge(side, _over_reps(sides))
    base = reps * period
    for i, p in enumerate(params["tail"]):
        if constrain is not None:
            p = constrain(p)
        kind, ffn = cfg.layer_sig(base + i)
        h, s, _ = _apply_block(p, h, cfg, kind, ffn)
        side = _merge(side, s)
    h = L.rmsnorm(params["final_norm"], h)
    logits = h @ params["lm_head"].astype(adt)
    return logits, side


def forward(params, cfg: ModelConfig, tokens=None, embeds=None,
            remat: bool = True, constrain=None):
    """Full-sequence forward -> (logits, aux_loss)."""
    logits, side = _forward(params, cfg, tokens, embeds, remat, constrain)
    return logits, side["aux"]


def loss_fn(params, cfg: ModelConfig, batch, remat: bool = True,
            constrain=None):
    """batch: {"tokens": (B,S)} or {"embeds": (B,S,D)}, plus "labels": (B,S).
    Returns (loss, metrics): ``ce``, ``aux``, ``loss`` and, where the model
    has expert layers, their counters (``moe.moe_layer``) over all of
    them."""
    logits, side = _forward(params, cfg, tokens=batch.get("tokens"),
                            embeds=batch.get("embeds"), remat=remat,
                            constrain=constrain)
    aux = side.pop("aux")
    labels = batch["labels"]
    # CE via one-hot-einsum + logsumexp: take_along_axis would gather over
    # the vocab dim, which is model-sharded — the one-hot product reduces
    # shard-locally instead (then a tiny psum over model shards).
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    picked = jnp.einsum("bsv,bsv->bs", logits, onehot)
    ll = picked - lse
    mask = batch.get("loss_mask", jnp.ones_like(ll))
    ce = -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    loss = ce + aux
    return loss, {"ce": ce, "aux": aux, "loss": loss, **side}


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------


def _check_serving(cfg: ModelConfig):
    """The serving path keeps no latent cache and runs no leading dense
    layers."""
    if "mla" in cfg.block_pattern or cfg.first_dense_layers:
        raise NotImplementedError(
            f"{cfg.name}: serving (init_cache / prefill / decode_step) does "
            "not take latent attention or leading dense layers")


def _cache_len(cfg: ModelConfig, kind: str, s_max: int) -> int:
    if kind == "swa":
        return min(cfg.sliding_window, s_max)
    return s_max


def _init_layer_cache(cfg: ModelConfig, kind: str, B: int, s_max: int, dtype):
    hd, KV = cfg.hd, cfg.num_kv_heads
    if kind in ("attn", "swa"):
        n = _cache_len(cfg, kind, s_max)
        return {"k": jnp.zeros((B, n, KV, hd), dtype),
                "v": jnp.zeros((B, n, KV, hd), dtype)}
    if kind == "mamba":
        return {"ssm": jnp.zeros((B, cfg.d_inner, cfg.ssm_state_dim),
                                 jnp.float32),
                "conv": jnp.zeros((B, cfg.ssm_conv_width, cfg.d_inner), dtype)}
    if kind == "mlstm":
        return X.mlstm_init_state(B, cfg)
    if kind == "slstm":
        return X.slstm_init_state(B, cfg)
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, B: int, s_max: int, dtype=None):
    _check_serving(cfg)
    dtype = dtype or jnp.dtype(cfg.activation_dtype)
    period = cfg.pattern_period
    reps, tail = divmod(cfg.num_layers, period)
    stack = []
    for pos in range(period if reps else 0):
        kind = cfg.block_kind(pos)
        one = _init_layer_cache(cfg, kind, B, s_max, dtype)
        stack.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x, (reps,) + x.shape), one))
    tail_caches = [
        _init_layer_cache(cfg, cfg.block_kind(reps * period + i), B, s_max,
                          dtype)
        for i in range(tail)
    ]
    return {"stack": stack, "tail": tail_caches}


def _store_prefill(cfg: ModelConfig, kind: str, contrib, cache, s_max: int):
    """Write a full-sequence cache contribution into a layer cache."""
    if kind in ("attn", "swa"):
        _, k, v = contrib
        n = cache["k"].shape[1]
        T = k.shape[1]
        if T >= n:
            # keep last n entries, ring-ordered by absolute position
            ring = (jnp.arange(T - n, T)) % n
            ck = jnp.zeros_like(cache["k"]).at[:, ring].set(
                k[:, -n:].astype(cache["k"].dtype))
            cv = jnp.zeros_like(cache["v"]).at[:, ring].set(
                v[:, -n:].astype(cache["v"].dtype))
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), 0, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), 0, axis=1)
        return {"k": ck, "v": cv}
    if kind == "mamba":
        _, ssm_state, conv_tail = contrib
        W = cfg.ssm_conv_width
        conv = jnp.zeros_like(cache["conv"])
        conv = jax.lax.dynamic_update_slice_in_dim(
            conv, conv_tail.astype(conv.dtype), W - conv_tail.shape[1], axis=1)
        return {"ssm": ssm_state, "conv": conv}
    # xLSTM states pass through directly
    return contrib[1]


def prefill(params, cfg: ModelConfig, tokens=None, embeds=None, *,
            s_max: Optional[int] = None, cache_dtype=None, constrain=None):
    """Run the prompt, return (last-position logits, cache, next_pos)."""
    adt = jnp.dtype(cfg.activation_dtype)
    if embeds is not None:
        h = embeds.astype(adt)
        B, T = embeds.shape[:2]
    else:
        h = params["embed"][tokens].astype(adt)
        B, T = tokens.shape
    s_max = s_max or T
    cache = init_cache(cfg, B, s_max, cache_dtype)
    period = cfg.pattern_period
    reps = cfg.num_layers // period

    def period_body(h, xs):
        p_rep, c_rep = xs
        if constrain is not None:
            p_rep = constrain(p_rep)
        new_c = []
        for pos in range(period):
            kind, ffn = cfg.layer_sig(pos)
            h, _, contrib = _apply_block(p_rep[pos], h, cfg, kind, ffn)
            new_c.append(_store_prefill(cfg, kind, contrib, c_rep[pos], s_max))
        return h, new_c

    if reps:
        h, new_stack = jax.lax.scan(period_body, h,
                                    (params["stack"], cache["stack"]))
        cache["stack"] = new_stack
    base = reps * period
    for i, p in enumerate(params["tail"]):
        if constrain is not None:
            p = constrain(p)
        kind, ffn = cfg.layer_sig(base + i)
        h, _, contrib = _apply_block(p, h, cfg, kind, ffn)
        cache["tail"][i] = _store_prefill(cfg, kind, contrib,
                                          cache["tail"][i], s_max)
    h = L.rmsnorm(params["final_norm"], h[:, -1:])
    logits = h @ params["lm_head"].astype(adt)
    return logits, cache, T


def _decode_block(p, h, cfg: ModelConfig, kind: str, ffn: str, cache, pos):
    normed = L.rmsnorm(p["norm1"], h)
    if kind in ("attn", "swa"):
        n = cache["k"].shape[1]
        # sliding-window layers use a ring buffer once the cache is
        # window-sized; full-attention layers write at the absolute position
        write_idx = pos % n if kind == "swa" else pos
        core_out, ck, cv = L.attention_decode(
            p["core"], normed, cache["k"], cache["v"], pos, write_idx, cfg)
        cache = {"k": ck, "v": cv}
    elif kind == "mamba":
        core_out, ssm, conv = S.mamba_decode(p["core"], normed, cache["ssm"],
                                             cache["conv"], cfg)
        cache = {"ssm": ssm, "conv": conv}
    elif kind == "mlstm":
        core_out, cache = X.mlstm_forward(p["core"], normed, cfg, state=cache)
    elif kind == "slstm":
        core_out, cache = X.slstm_forward(p["core"], normed, cfg, state=cache)
    else:
        raise ValueError(kind)
    if cfg.parallel_block and ffn != "none":
        if ffn == "moe":
            f_out, _ = M.moe_ffn(p["ffn"], normed, cfg)
        else:
            f_out = L.mlp(p["ffn"], normed)
        return h + core_out + f_out, cache
    h = h + core_out
    if ffn == "mlp":
        h = h + L.mlp(p["ffn"], L.rmsnorm(p["norm2"], h))
    elif ffn == "moe":
        f_out, _ = M.moe_ffn(p["ffn"], L.rmsnorm(p["norm2"], h), cfg)
        h = h + f_out
    return h, cache


def decode_step(params, cfg: ModelConfig, cache, pos, tokens=None,
                embeds=None, constrain=None):
    """One decode step.  tokens: (B,1) ints or embeds: (B,1,D).
    pos: scalar int32 (current absolute position).  Returns (logits, cache)."""
    _check_serving(cfg)
    adt = jnp.dtype(cfg.activation_dtype)
    if embeds is not None:
        h = embeds.astype(adt)
    else:
        h = params["embed"][tokens].astype(adt)
    period = cfg.pattern_period
    reps = cfg.num_layers // period

    def period_body(h, xs):
        p_rep, c_rep = xs
        if constrain is not None:
            p_rep = constrain(p_rep)
        new_c = []
        for posn in range(period):
            kind, ffn = cfg.layer_sig(posn)
            h, c = _decode_block(p_rep[posn], h, cfg, kind, ffn, c_rep[posn],
                                 pos)
            new_c.append(c)
        return h, new_c

    new_cache = dict(cache)
    if reps:
        h, new_stack = jax.lax.scan(period_body, h,
                                    (params["stack"], cache["stack"]))
        new_cache["stack"] = new_stack
    base = reps * period
    new_tail = []
    for i, p in enumerate(params["tail"]):
        if constrain is not None:
            p = constrain(p)
        kind, ffn = cfg.layer_sig(base + i)
        h, c = _decode_block(p, h, cfg, kind, ffn, cache["tail"][i], pos)
        new_tail.append(c)
    new_cache["tail"] = new_tail
    h = L.rmsnorm(params["final_norm"], h)
    logits = h @ params["lm_head"].astype(adt)
    return logits, new_cache
