"""Family ``deepseek_v2``: DeepSeek-V2's decoder (arXiv:2405.04434),
written from the equations in plain ``jax.numpy``; nothing here imports
the program.

A layer is pre-norm: multi-head latent attention, then an FFN.  The
first ``first_dense_layers`` layers (the tree's ``lead``) have a SwiGLU
MLP of ``d_ff``; the rest follow the pattern, with an expert layer.

* Latent attention, with no query compression: ``q = h W_q`` gives each
  head ``qk_nope_head_dim`` plain and ``qk_rope_head_dim`` rotary dims;
  ``[c | k_pe] = h W_kva``; ``[k_nope | v] = RMSNorm(c) W_kvb``.  The
  rotary key ``k_pe`` is one head shared by all.  Rotary frequencies and
  the softmax scale are YaRN's (``rope_scaling_factor`` s, the original
  context, beta_fast / beta_slow, mscale, mscale_all_dim): scale
  ``mscale(s, mscale_all_dim)^2 / sqrt(nope + rope)``.  Rotary pairs are
  the two halves of the rotary dims.
* The expert layer: softmax scores over all ``num_experts``, each token's
  ``experts_per_token`` highest (greedily), the gates renormalized only
  under ``norm_topk_prob`` and times ``routed_scaling_factor``.  The
  layer holds experts ``[expert_offset, expert_offset + experts_held)``:
  the output is the sum over the held experts of each one's SwiGLU on
  every token times its dense gate (zero where the token did not choose
  it), plus the shared SwiGLU of ``num_shared_experts`` times the expert
  width.  No token is dropped.
* The loss: mean next-token cross-entropy plus, for every expert layer,
  the sequence-wise balance term ``router_aux_coef * mean over sequences
  of sum_e f_e P_e``, ``f_e = E / (K T)`` times the sequence's
  assignments to e, ``P_e`` its mean score of e, over all E experts.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference
from bench.families.decoder import rmsnorm
from bench.reference import layer_sig, period

Q_BLOCK = 1024       # queries per block of the attention's scores


def _check(model: dict):
    if model.get("capacity_factor") is not None:
        raise ValueError("the reference drops no token: capacity_factor "
                         "must be null")
    if model.get("router_aux") != "seq":
        raise ValueError("the reference's balance term is the sequence-wise "
                         "one: router_aux must be 'seq'")
    for kind in model["block_pattern"]:
        if kind != "mla":
            raise ValueError(f"no reference for block kind {kind!r}")


def held(model: dict) -> int:
    return model.get("experts_held") or model["num_experts"]


def _block_shapes(model: dict, kind: str, ffn: str) -> dict:
    D, H, r = model["d_model"], model["num_heads"], model["kv_latent_dim"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    vd = model["v_head_dim"]
    block = {"norm1": {"scale": (D,)},
             "core": {"wq": (D, H * (nope + rope)), "wkv_a": (D, r + rope),
                      "kv_norm": {"scale": (r,)},
                      "wkv_b": (r, H * (nope + vd)), "wo": (H * vd, D)},
             "norm2": {"scale": (D,)}}
    if ffn == "mlp":
        F = model["d_ff"]
        block["ffn"] = {"w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)}
    elif ffn == "moe":
        E, G, F = model["num_experts"], held(model), model["moe_d_ff"]
        S = F * model["num_shared_experts"]
        block["ffn"] = {"router": (D, E), "w_gate": (G, D, F),
                        "w_up": (G, D, F), "w_down": (G, F, D),
                        "shared": {"w_gate": (D, S), "w_up": (D, S),
                                   "w_down": (S, D)}}
    else:
        raise ValueError(f"no reference for ffn kind {ffn!r}")
    return block


def n_lead(model: dict) -> int:
    return model.get("first_dense_layers", 0)


def param_shapes(model: dict) -> dict:
    _check(model)
    lead = n_lead(model)
    shapes = reference.pattern_shapes(
        dict(model, num_layers=model["num_layers"] - lead), _block_shapes)
    if lead:
        shapes["lead"] = [_block_shapes(model, "mla", "mlp")
                          for _ in range(lead)]
    return shapes


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def yarn_mscale(s: float, m: float) -> float:
    return 1.0 if s <= 1 else 0.1 * m * math.log(s) + 1.0


def yarn(model: dict, T: int):
    """cos, sin (T, rope/2) of YaRN's rotary embedding."""
    d, base = model["qk_rope_head_dim"], model["rope_theta"]
    s = model.get("rope_scaling_factor", 1.0)
    pos_freq = base ** (np.arange(0, d, 2) / d)
    extra, inter = 1.0 / pos_freq, 1.0 / (s * pos_freq)
    if s > 1:
        orig = model["rope_original_max_position"]

        def dim_of(rotations):
            return (d * math.log(orig / (rotations * 2 * math.pi))
                    / (2 * math.log(base)))

        lo = max(math.floor(dim_of(model["rope_beta_fast"])), 0)
        hi = min(math.ceil(dim_of(model["rope_beta_slow"])), d - 1)
        ramp = np.clip((np.arange(d // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
        inv = inter * ramp + extra * (1 - ramp)
    else:
        inv = extra
    ang = np.arange(T)[:, None] * inv[None, :]
    att = yarn_mscale(s, model.get("rope_mscale", 1.0)) / yarn_mscale(
        s, model.get("rope_mscale_all_dim", 0.0))
    return (jnp.asarray(np.cos(ang) * att, jnp.float32),
            jnp.asarray(np.sin(ang) * att, jnp.float32))


def rotate(x, cos, sin):
    """(x1, x2) -> (x1 cos - x2 sin, x1 sin + x2 cos) over the halves of
    the last dim; x: (B, T, heads, d), cos/sin (T, d/2)."""
    c, s = cos[:, None, :], sin[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c],
                           -1).astype(x.dtype)


def mla(p, x, model):
    B, T, _ = x.shape
    H, r = model["num_heads"], model["kv_latent_dim"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    vd = model["v_head_dim"]
    q = (x @ p["wq"]).reshape(B, T, H, nope + rope)
    kv_a = x @ p["wkv_a"]
    kv = (rmsnorm(p["kv_norm"]["scale"], kv_a[..., :r]) @ p["wkv_b"]
          ).reshape(B, T, H, nope + vd)
    cos, sin = yarn(model, T)
    q_nope, q_pe = q[..., :nope], rotate(q[..., nope:], cos, sin)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_pe = rotate(kv_a[..., None, r:], cos, sin)[:, :, 0]     # (B, T, rope)
    s = model.get("rope_scaling_factor", 1.0)
    scale = yarn_mscale(s, model.get("rope_mscale_all_dim", 0.0)) ** 2 \
        / math.sqrt(nope + rope)
    outs = []
    for t0 in range(0, T, Q_BLOCK):
        t1 = min(T, t0 + Q_BLOCK)
        scores = (jnp.einsum("bthd,bshd->bhts", q_nope[:, t0:t1], k_nope)
                  + jnp.einsum("bthd,bsd->bhts", q_pe[:, t0:t1], k_pe)
                  ) * scale
        causal = np.arange(t0, t1)[:, None] >= np.arange(T)[None, :]
        w = jax.nn.softmax(jnp.where(causal, scores.astype(jnp.float32),
                                     -jnp.inf), -1).astype(x.dtype)
        outs.append(jnp.einsum("bhts,bshd->bthd", w, v))
    o = jnp.concatenate(outs, 1)
    return o.reshape(B, T, H * vd) @ p["wo"]


def swiglu(f, y):
    return (jax.nn.silu(y @ f["w_gate"]) * (y @ f["w_up"])) @ f["w_down"]


def moe(f, y, model):
    """(output, balance term) of the expert layer on ``y`` (B, T, D)."""
    B, T, _ = y.shape
    E, K = model["num_experts"], model["experts_per_token"]
    off = model.get("expert_offset", 0)
    probs = jax.nn.softmax((y @ f["router"]).astype(jnp.float32), -1)
    top, idx = jax.lax.top_k(probs, K)
    if model.get("norm_topk_prob", True):
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * model.get("routed_scaling_factor", 1.0)
    gates = jnp.sum(jax.nn.one_hot(idx, E) * top[..., None], -2)
    out = swiglu(f["shared"], y)
    for e in range(held(model)):
        expert = {w: f[w][e] for w in ("w_gate", "w_up", "w_down")}
        out = out + gates[..., off + e, None].astype(y.dtype) * swiglu(
            expert, y)
    counts = jnp.sum(jax.nn.one_hot(idx, E), (1, 2))          # (B, E)
    share = counts * E / (K * T)
    aux = model["router_aux_coef"] * jnp.mean(
        jnp.sum(share * jnp.mean(probs, 1), -1))
    return out, aux


def block(p, h, model, ffn):
    """(h, balance term) after one layer."""
    h = h + mla(p["core"], rmsnorm(p["norm1"]["scale"], h), model)
    y = rmsnorm(p["norm2"]["scale"], h)
    if ffn == "mlp":
        return h + swiglu(p["ffn"], y), 0.0
    out, aux = moe(p["ffn"], y, model)
    return h + out, aux


def loss(params, tokens, labels, model):
    """Mean next-token cross-entropy plus every expert layer's balance
    term."""
    h = params["embed"][tokens]
    aux = 0.0
    for p in params.get("lead", []):
        h, _ = jax.checkpoint(lambda p, h: block(p, h, model, "mlp"))(p, h)
    P = period(model)

    @jax.checkpoint
    def rep(h, p_rep):
        a = 0.0
        for pos in range(P):
            h, b = block(p_rep[pos], h, model, layer_sig(model, pos)[1])
            a = a + b
        return h, a

    if params["stack"]:
        h, auxs = jax.lax.scan(rep, h, params["stack"])
        aux = aux + jnp.sum(auxs)
    base = ((model["num_layers"] - n_lead(model)) // P) * P
    for i, p in enumerate(params["tail"]):
        h, a = block(p, h, model, layer_sig(model, base + i)[1])
        aux = aux + a
    logits = (rmsnorm(params["final_norm"]["scale"], h)
              @ params["lm_head"]).astype(jnp.float32)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - picked) + aux


# ---------------------------------------------------------------------------
# the work: matmul FLOPs of the forward pass
# ---------------------------------------------------------------------------


def forward_flops_per_token(model: dict, seq: int) -> float:
    """2 FLOPs per weight per token for every weight matrix but the
    embedding table, each expert stack counted for the ``K * held / E``
    of its experts a token reaches here; and per layer the scores over
    ``nope + rope`` dims and the values over ``v_head_dim`` dims, over the
    full (unmasked) sequence: ``2 * seq * H * (nope + rope + v)``."""
    K, E = model["experts_per_token"], model["num_experts"]
    flops = 0.0
    for name, shape in zip(*reference.leaf_paths(param_shapes(model))):
        core = shape[1:] if name.startswith("stack/") else shape
        if name == "embed" or len(core) < 2:
            continue
        n = float(math.prod(shape))
        if len(core) == 3:                 # (experts held, in, out)
            n *= K / E
        flops += 2.0 * n
    per_layer = 2.0 * seq * model["num_heads"] * (
        model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
        + model["v_head_dim"])
    return flops + model["num_layers"] * per_layer
