"""Family ``decoder``: a pre-norm decoder of RMSNorm, rotary attention
and SwiGLU, written from the equations in plain ``jax.numpy``; nothing
here imports the program.

A family gives the harness three functions of the configuration's
``model`` dict (``bench/spec.py``): ``param_shapes``, the program's
tree of parameter shapes; ``loss``, the forward pass and its mean
next-token cross-entropy in float32; ``forward_flops_per_token``, the
matmul FLOPs of one token's forward pass.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference
from bench.reference import layer_sig, period


def head_dim(model: dict) -> int:
    return model.get("head_dim") or model["d_model"] // model["num_heads"]


def _block_shapes(model: dict, kind: str, ffn: str) -> dict:
    D, H, KV = model["d_model"], model["num_heads"], model["num_kv_heads"]
    hd = head_dim(model)
    block = {"norm1": {"scale": (D,)}}
    if kind == "attn":
        block["core"] = {"wq": (D, H * hd), "wk": (D, KV * hd),
                         "wv": (D, KV * hd), "wo": (H * hd, D)}
    else:
        raise ValueError(f"no reference for block kind {kind!r}")
    if ffn == "mlp":
        F = model["d_ff"]
        block["norm2"] = {"scale": (D,)}
        block["ffn"] = {"w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)}
    elif ffn != "none":
        raise ValueError(f"no reference for ffn kind {ffn!r}")
    return block


def param_shapes(model: dict) -> dict:
    return reference.pattern_shapes(model, _block_shapes)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def rmsnorm(scale, x):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + 1e-6)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rope(x, theta: float):
    """Rotary embedding over the whole head dim, halves rotated as pairs:
    (x1, x2) -> (x1 cos - x2 sin, x1 sin + x2 cos).  x: (B, T, H, hd)."""
    T, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c],
                           -1).astype(x.dtype)


def attention(p, x, model):
    B, T, _ = x.shape
    H, KV, hd = model["num_heads"], model["num_kv_heads"], head_dim(model)
    q = rope((x @ p["wq"]).reshape(B, T, H, hd), model["rope_theta"])
    k = rope((x @ p["wk"]).reshape(B, T, KV, hd), model["rope_theta"])
    v = (x @ p["wv"]).reshape(B, T, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = (jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(hd)).astype(
        jnp.float32)
    causal = jnp.tril(jnp.ones((T, T), bool))
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1).astype(x.dtype)
    o = jnp.einsum("bhts,bshd->bthd", w, v)
    return o.reshape(B, T, H * hd) @ p["wo"]


def block(p, h, model, ffn):
    h = h + attention(p["core"], rmsnorm(p["norm1"]["scale"], h), model)
    if ffn == "mlp":
        y = rmsnorm(p["norm2"]["scale"], h)
        f = p["ffn"]
        h = h + (jax.nn.silu(y @ f["w_gate"]) * (y @ f["w_up"])) @ f["w_down"]
    return h


def loss(params, tokens, labels, model):
    """Mean next-token cross-entropy over every position of the batch."""
    h = params["embed"][tokens]
    P = period(model)

    @jax.checkpoint
    def rep(h, p_rep):
        for pos in range(P):
            h = block(p_rep[pos], h, model, layer_sig(model, pos)[1])
        return h, None

    if params["stack"]:
        h, _ = jax.lax.scan(rep, h, params["stack"])
    base = (model["num_layers"] // P) * P
    for i, p in enumerate(params["tail"]):
        h = block(p, h, model, layer_sig(model, base + i)[1])
    logits = (rmsnorm(params["final_norm"]["scale"], h)
              @ params["lm_head"]).astype(jnp.float32)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)


# ---------------------------------------------------------------------------
# the work: matmul FLOPs of the forward pass
# ---------------------------------------------------------------------------


def _weights(model: dict) -> int:
    """Elements of the weight matrices that multiply activations (the
    embedding table, norms and biases excluded)."""
    total = 0
    for name, shape in zip(*reference.leaf_paths(param_shapes(model))):
        core = shape[1:] if name.startswith("stack/") else shape
        if name != "embed" and len(core) >= 2:
            total += int(np.prod(shape))
    return total


def forward_flops_per_token(model: dict, seq: int) -> float:
    """Every weight matrix except the embedding table (a gather, no
    product), the LM head included: 2 FLOPs per weight per token; the
    attention scores and values, over the full (unmasked) sequence the
    step computes: 2 * 2 * seq * d_attn per token and layer."""
    hd = head_dim(model)
    H = model["num_heads"]
    flops = 2.0 * _weights(model)
    for layer in range(model["num_layers"]):
        kind, _ = layer_sig(model, layer)
        if kind != "attn":
            raise ValueError(f"no FLOP count for block kind {kind!r}")
        flops += 4.0 * seq * H * hd
    return flops
