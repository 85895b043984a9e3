"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1) with
YaRN rotary scaling, for training and full-sequence forward passes.

With no query compression::

    q              = h W_q                      -> (H, nope | rope)
    [c_kv | k_pe]  = h W_kva                    -> (latent | rope)
    c_kv           = RMSNorm(c_kv)
    [k_nope | v]   = c_kv W_kvb                 -> (H, nope | v)

The rotary part of each query and the one rotary key head, shared by all
heads, are rotated by YaRN's frequencies; each head scores over
``nope + rope`` dims with YaRN's softmax scale ``mscale(s, m_all)^2 /
sqrt(nope + rope)`` and reads ``v_head_dim`` dims of values.  Rotary
pairs are the two halves of the rotary dims (rotate-half), where the
published code de-interleaves them first: a fixed permutation of the
rotary columns of ``W_q`` and ``W_kva``.  No latent cache is kept, so
the serving path does not take this block.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.layers import (_SDPA_CHUNK, _dense_init, _sdpa,
                                 _sdpa_chunked, apply_rope, causal_mask,
                                 init_rmsnorm, rmsnorm)


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg: ModelConfig) -> np.ndarray:
    """Per-pair rotary frequencies: YaRN's blend of the interpolated
    (``1 / (s base^(2i/d))``) and the original frequencies, ramped between
    the correction dims of ``beta_fast`` and ``beta_slow`` rotations."""
    d, base, s = cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_scaling_factor
    extra = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if s <= 1:
        return extra.astype(np.float32)

    def corr_dim(rot):
        return d * math.log(cfg.rope_original_max_position
                            / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(corr_dim(cfg.rope_beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                  # 1: original frequency
    return (extra / s * (1.0 - keep) + extra * keep).astype(np.float32)


def yarn_angles(positions, cfg: ModelConfig):
    """cos/sin (T, rope/2) of YaRN, scaled by its attention factor
    ``mscale(s, m) / mscale(s, m_all)``."""
    ang = positions[:, None].astype(jnp.float32) * jnp.asarray(
        yarn_inv_freq(cfg))
    s = cfg.rope_scaling_factor
    m = yarn_mscale(s, cfg.rope_mscale) / yarn_mscale(
        s, cfg.rope_mscale_all_dim)
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def softmax_scale(cfg: ModelConfig) -> float:
    m = yarn_mscale(cfg.rope_scaling_factor, cfg.rope_mscale_all_dim)
    return m * m / math.sqrt(cfg.mla_q_dim)


def init_mla(key, cfg: ModelConfig, dtype):
    D, H, r = cfg.d_model, cfg.num_heads, cfg.kv_latent_dim
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": _dense_init(ks[0], (D, H * (nope + rope)), dtype),
        "wkv_a": _dense_init(ks[1], (D, r + rope), dtype),
        "kv_norm": init_rmsnorm(r, dtype),
        "wkv_b": _dense_init(ks[2], (r, H * (nope + vd)), dtype),
        "wo": _dense_init(ks[3], (H * vd, D), dtype),
    }


def mla(p, x, cfg: ModelConfig, positions=None):
    """x: (B, T, D) -> (B, T, D), causal over the whole sequence; queries
    are blocked as ``layers.attention`` blocks them at long T."""
    B, T, _ = x.shape
    H, r = cfg.num_heads, cfg.kv_latent_dim
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    with jax.named_scope("mla"):
        q = (x @ p["wq"]).reshape(B, T, H, nope + rope)
        kv_a = x @ p["wkv_a"]
        c_kv = rmsnorm(p["kv_norm"], kv_a[..., :r])
        kv = (c_kv @ p["wkv_b"]).reshape(B, T, H, nope + vd)
        if positions is None:
            positions = jnp.arange(T)
        cos, sin = yarn_angles(positions, cfg)
        q_pe = apply_rope(q[..., nope:], cos, sin)
        k_pe = apply_rope(kv_a[..., None, r:], cos, sin)
        # _sdpa divides by sqrt(nope + rope): fold YaRN's factor into q
        fold = softmax_scale(cfg) * math.sqrt(nope + rope)
        q = jnp.concatenate([q[..., :nope], q_pe], -1) * fold
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (B, T, H, rope))], -1)
        v = kv[..., nope:]
        if T > _SDPA_CHUNK and T % _SDPA_CHUNK == 0:
            out = _sdpa_chunked(q, k, v, cfg, 0, _SDPA_CHUNK, remat=True)
        else:
            out = _sdpa(q, k, v, causal_mask(T, T), cfg)
        return out.reshape(B, T, H * vd) @ p["wo"]
