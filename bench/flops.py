"""Operations that the benchmarked work requires, from the
configuration's shapes alone.

Matmul FLOPs of a training step (for ``mfu``): the forward pass's
matrix products times three (forward, and the two products of the
backward pass), with no recomputation counted:

* every weight matrix except the embedding table (a gather, no
  product), the LM head included: 2 FLOPs per weight per token;
* attention scores and values, over the full (unmasked) sequence the
  step computes: 2 * 2 * seq * d_attn per token and layer.
"""
from __future__ import annotations

import numpy as np

from bench import reference


def _weights(model: dict) -> int:
    """Elements of the weight matrices that multiply activations (the
    embedding table, norms and biases excluded)."""
    total = 0
    for name, shape in zip(*reference.leaf_shapes(model)):
        core = shape[1:] if name.startswith("stack/") else shape
        if name != "embed" and len(core) >= 2:
            total += int(np.prod(shape))
    return total


def forward_flops_per_token(model: dict, seq: int) -> float:
    hd = reference.head_dim(model)
    H = model["num_heads"]
    flops = 2.0 * _weights(model)
    for layer in range(model["num_layers"]):
        kind, _ = reference.layer_sig(model, layer)
        if kind != "attn":
            raise ValueError(f"no FLOP count for block kind {kind!r}")
        flops += 4.0 * seq * H * hd
    return flops


def train_flops_per_token(model: dict, seq: int) -> float:
    return 3.0 * forward_flops_per_token(model, seq)
