"""Small stand-ins of the benchmark's cells, for tests on the CPU: the
same model kind, job and limits as the cell, at widths a test holds."""
import json

from bench import spec

DENSE = {"name": "tiny-dense", "arch_type": "dense", "num_layers": 2,
         "d_model": 64, "num_heads": 4, "num_kv_heads": 4, "d_ff": 128,
         "vocab_size": 256, "rope_theta": 10000.0,
         "block_pattern": ["attn"], "ffn_pattern": ["mlp"]}
# the decoder with a routed mixture of experts in every second layer
# (``bench/tests/moe_family.py``): 4 experts, 2 a token, one shared, room
# for every token (no drops)
MOE = dict(DENSE, name="tiny-moe", arch_type="moe", num_layers=3,
           ffn_pattern=["mlp", "moe"], num_experts=4, experts_per_token=2,
           num_shared_experts=1, moe_d_ff=64, capacity_factor=2.0,
           router_aux_coef=0.01)
STANDS_FOR = {"stablelm_efjnp_1chip": DENSE}


def cell(name: str) -> dict:
    """The cell ``name`` as ``spec.load`` gives it, with its model
    replaced by the small one and its sequence cut to 32 (ratio 0.01,
    so that each small leaf still selects a few coordinates; lr 0.01,
    so that one step moves a weight by a share of it closer to the
    cell's, where bfloat16 holds no step of the embedding)."""
    full = spec.load(name)
    job = dict(full["job"], seq=32, ratio=0.01, lr=0.01)
    return dict(full, model=STANDS_FOR[name], job=job)


def limits(name: str) -> dict:
    with open(spec.HERE / "limits" / f"{name}.json") as f:
        return json.load(f)["limits"]
