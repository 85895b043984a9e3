"""A second family, for the tests only: the decoder of
``bench/families/decoder.py`` with a routed mixture of experts in place
of its MLP, written from the equations; the tests copy it into a
temporary root as ``bench/families/moe.py``.

The FFN: router probabilities ``p = softmax(x W_r)``; each token goes
to its ``experts_per_token`` most probable experts, their gates
renormalized to sum to one; the output is the gated sum of those
experts' SwiGLU MLPs, plus a shared SwiGLU MLP of ``num_shared_experts``
times the expert width.  The loss adds the Switch load-balance term
``router_aux_coef * E * sum_e mean(p_e) * share of tokens whose first
choice is e`` of every MoE layer.  No token is dropped: the
configuration's ``capacity_factor`` has to give each expert room for
every token (``capacity_factor * experts_per_token >= num_experts``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import reference
from bench.families import decoder
from bench.reference import layer_sig, period


def _check(model: dict):
    E, K = model["num_experts"], model["experts_per_token"]
    if model["capacity_factor"] * K < E:
        raise ValueError("the reference drops no token: capacity_factor * "
                         "experts_per_token must reach num_experts")


def _block_shapes(model: dict, kind: str, ffn: str) -> dict:
    if ffn != "moe":
        return decoder._block_shapes(model, kind, ffn)
    _check(model)
    D, E, F = model["d_model"], model["num_experts"], model["moe_d_ff"]
    block = decoder._block_shapes(model, kind, "none")
    block["norm2"] = {"scale": (D,)}
    block["ffn"] = {"router": (D, E), "w_gate": (E, D, F),
                    "w_up": (E, D, F), "w_down": (E, F, D)}
    if model["num_shared_experts"]:
        S = F * model["num_shared_experts"]
        block["ffn"]["shared"] = {"w_gate": (D, S), "w_up": (D, S),
                                  "w_down": (S, D)}
    return block


def param_shapes(model: dict) -> dict:
    return reference.pattern_shapes(model, _block_shapes)


def _swiglu(f, y):
    return (jax.nn.silu(y @ f["w_gate"]) * (y @ f["w_up"])) @ f["w_down"]


def moe(f, y, model):
    """(output, load-balance term) of the MoE FFN on ``y`` (B, T, D)."""
    E, K = model["num_experts"], model["experts_per_token"]
    probs = jax.nn.softmax((y @ f["router"]).astype(jnp.float32), -1)
    top, idx = jax.lax.top_k(probs, K)
    top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(idx, E) * top[..., None], -2)
    out = sum(gates[..., e, None].astype(y.dtype) * _swiglu(
        {w: f[w][e] for w in ("w_gate", "w_up", "w_down")}, y)
        for e in range(E))
    if "shared" in f:
        out = out + _swiglu(f["shared"], y)
    first = jnp.mean(jax.nn.one_hot(idx[..., 0], E), (0, 1))
    aux = model["router_aux_coef"] * E * jnp.sum(jnp.mean(probs, (0, 1))
                                                  * first)
    return out, aux


def block(p, h, model, ffn):
    """(h, load-balance term) after one layer."""
    if ffn != "moe":
        return decoder.block(p, h, model, ffn), 0.0
    h = h + decoder.attention(p["core"], decoder.rmsnorm(
        p["norm1"]["scale"], h), model)
    out, aux = moe(p["ffn"], decoder.rmsnorm(p["norm2"]["scale"], h), model)
    return h + out, aux


def loss(params, tokens, labels, model):
    """Mean next-token cross-entropy plus every layer's load-balance
    term."""
    h = params["embed"][tokens]
    P = period(model)

    @jax.checkpoint
    def rep(h, p_rep):
        aux = 0.0
        for pos in range(P):
            h, a = block(p_rep[pos], h, model, layer_sig(model, pos)[1])
            aux = aux + a
        return h, aux

    aux = 0.0
    if params["stack"]:
        h, auxs = jax.lax.scan(rep, h, params["stack"])
        aux = jnp.sum(auxs)
    base = (model["num_layers"] // P) * P
    for i, p in enumerate(params["tail"]):
        h, a = block(p, h, model, layer_sig(model, base + i)[1])
        aux = aux + a
    logits = (decoder.rmsnorm(params["final_norm"]["scale"], h)
              @ params["lm_head"]).astype(jnp.float32)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - picked) + aux


def forward_flops_per_token(model: dict, seq: int) -> float:
    """The decoder's count with each MoE layer's experts counted for the
    ``experts_per_token`` a token is routed to, not all of them."""
    flops = 0.0
    for name, shape in zip(*reference.leaf_paths(param_shapes(model))):
        core = shape[1:] if name.startswith("stack/") else shape
        if name == "embed" or len(core) < 2:
            continue
        n = float(math.prod(shape))
        if len(core) == 3:                 # (experts, in, out)
            n *= model["experts_per_token"] / model["num_experts"]
        flops += 2.0 * n
    for layer in range(model["num_layers"]):
        flops += 4.0 * seq * model["num_heads"] * decoder.head_dim(model)
    return flops
