"""Operations that the benchmarked work requires, from the
configuration's shapes alone.

Matmul FLOPs of a training step (for ``mfu``): the forward pass's
matrix products times three (forward, and the two products of the
backward pass), with no recomputation counted.  The forward pass's
count is the configuration's family's (``forward_flops_per_token`` of
``bench/families/<family>.py``).
"""
from __future__ import annotations


def train_flops_per_token(family, model: dict, seq: int) -> float:
    return 3.0 * family.forward_flops_per_token(model, seq)
