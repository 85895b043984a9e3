"""Shared neural-net layers: RMSNorm, RoPE, GQA attention (full-causal and
sliding-window, with KV cache), SwiGLU MLP.  Pure-function style: params are
nested dicts of jnp arrays; init_* builds them, apply-side functions consume
them.  All control flow is jax.lax — every function jit/shard_map-safe."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def _dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (scale * jax.random.normal(key, shape)).astype(dtype)


def init_attention(key, cfg: ModelConfig, dtype):
    hd, H, KV, D = cfg.hd, cfg.num_heads, cfg.num_kv_heads, cfg.d_model
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (D, H * hd), dtype),
        "wk": _dense_init(ks[1], (D, KV * hd), dtype),
        "wv": _dense_init(ks[2], (D, KV * hd), dtype),
        "wo": _dense_init(ks[3], (H * hd, D), dtype),
    }
    if cfg.use_bias:
        p.update(bq=jnp.zeros((H * hd,), dtype), bk=jnp.zeros((KV * hd,), dtype),
                 bv=jnp.zeros((KV * hd,), dtype), bo=jnp.zeros((D,), dtype))
    return p


def init_mlp(key, d_model: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": _dense_init(k1, (d_model, d_ff), dtype),
        "w_up": _dense_init(k2, (d_model, d_ff), dtype),
        "w_down": _dense_init(k3, (d_ff, d_model), dtype),
    }


def init_rmsnorm(d: int, dtype):
    return {"scale": jnp.ones((d,), dtype)}


# ---------------------------------------------------------------------------
# apply
# ---------------------------------------------------------------------------


def rmsnorm(p, x, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * p["scale"].astype(jnp.float32)).astype(dt)


def rope_angles(positions, hd: int, theta: float):
    """positions: (...,) int -> cos/sin of shape (..., hd//2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (..., T, n_heads, hd); cos/sin: (..., T, hd//2) broadcast over heads."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[..., None, :], sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c],
                           axis=-1).astype(x.dtype)


def _qkv(p, x, cfg: ModelConfig):
    hd, H, KV = cfg.hd, cfg.num_heads, cfg.num_kv_heads
    B, T, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.use_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return (q.reshape(B, T, H, hd), k.reshape(B, T, KV, hd),
            v.reshape(B, T, KV, hd))


def _sdpa(q, k, v, mask, cfg: ModelConfig):
    """q: (B,T,H,hd), k/v: (B,S,KV,hd), mask: (T,S) or (B,T,S) bool."""
    hd = q.shape[-1]
    rep = cfg.num_heads // cfg.num_kv_heads
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(hd)
    logits = logits.astype(jnp.float32)
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None, None]
        elif mask.ndim == 3:
            mask = mask[:, None]
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


# chunk size above which full-sequence attention switches to the
# query-blocked scan (keeps the (T, S) logit tensor out of HBM)
_SDPA_CHUNK = 1024


def _sdpa_chunked(q, k, v, cfg: ModelConfig, window: int, chunk: int,
                  remat: bool = False):
    """Query-blocked causal attention: lax.scan over query chunks so only a
    (B, H, chunk, S) logit block is ever live — O(T·chunk) memory instead of
    O(T²).  This is the XLA-level analogue of flash attention's outer loop;
    it is what makes ``prefill_32k`` lowerable at sane HBM footprints.
    ``remat`` recomputes each block's scores in the backward pass instead
    of keeping every block's for it."""
    B, T, H, hd = q.shape
    S = k.shape[1]
    assert T % chunk == 0, (T, chunk)
    nch = T // chunk
    qc = jnp.moveaxis(q.reshape(B, nch, chunk, H, hd), 1, 0)

    kpos = jnp.arange(S)

    def body(_, inp):
        qi, start = inp
        qpos = start + jnp.arange(chunk) + (S - T)
        mask = kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        return None, _sdpa(qi, k, v, mask, cfg)

    if remat:
        body = jax.checkpoint(body)
    starts = jnp.arange(nch) * chunk
    _, out = jax.lax.scan(body, None, (qc, starts))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H, v.shape[-1])


def causal_mask(T: int, S: int, window: int = 0):
    """(T, S) bool; queries are the last T positions of the S keys."""
    qpos = jnp.arange(T)[:, None] + (S - T)
    kpos = jnp.arange(S)[None, :]
    m = kpos <= qpos
    if window:
        m &= kpos > qpos - window
    return m


def attention(p, x, cfg: ModelConfig, *, window: int = 0, positions=None):
    """Training/prefill self-attention over the full sequence."""
    B, T, D = x.shape
    q, k, v = _qkv(p, x, cfg)
    if positions is None:
        positions = jnp.arange(T)
    cos, sin = rope_angles(positions, cfg.hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    if T > _SDPA_CHUNK and T % _SDPA_CHUNK == 0:
        out = _sdpa_chunked(q, k, v, cfg, window, _SDPA_CHUNK)
    else:
        out = _sdpa(q, k, v, causal_mask(T, T, window), cfg)
    out = out.reshape(B, T, -1) @ p["wo"]
    if cfg.use_bias:
        out = out + p["bo"]
    return out, (k, v)


def attention_decode(p, x, cache_k, cache_v, pos, write_idx, cfg: ModelConfig):
    """Single-token decode: x (B,1,D); cache (B,S,KV,hd).

    ``pos`` is the absolute position (RoPE + causal mask); ``write_idx`` is
    the cache slot to write (== pos for full caches, pos % window for
    sliding-window ring buffers — the ring makes decode HBM traffic
    O(window) instead of O(S)).  Keys are cached post-RoPE, so attention
    over a ring-permuted cache is exact (softmax is permutation-invariant);
    the mask ``slot_count <= pos`` hides not-yet-written slots."""
    B, _, D = x.shape
    q, k, v = _qkv(p, x, cfg)
    cos, sin = rope_angles(jnp.asarray(pos)[None], cfg.hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k.astype(cache_k.dtype), write_idx, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v.astype(cache_v.dtype), write_idx, axis=1)
    S = cache_k.shape[1]
    mask = (jnp.arange(S) <= pos)[None, :]
    out = _sdpa(q, cache_k.astype(q.dtype), cache_v.astype(q.dtype), mask, cfg)
    out = out.reshape(B, 1, -1) @ p["wo"]
    if cfg.use_bias:
        out = out + p["bo"]
    return out, cache_k, cache_v


def mlp(p, x):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
