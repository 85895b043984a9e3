"""Model/architecture configuration dataclass shared by all 10 assigned
architectures (+ the paper's own small models)."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                     # dense | moe | hybrid | ssm | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None     # default d_model // num_heads

    # layer pattern, cycled over layers. entries:
    #   attn        full-causal GQA attention
    #   swa         sliding-window GQA attention
    #   mla         multi-head latent attention (DeepSeek-V2), training only
    #   mamba       selective-SSM (Mamba) block
    #   slstm/mlstm xLSTM blocks
    block_pattern: Tuple[str, ...] = ("attn",)
    # ffn per layer, cycled:  mlp | moe | none
    ffn_pattern: Tuple[str, ...] = ("mlp",)
    # leading layers of ffn "mlp" at d_ff that run, unrolled, before the
    # patterned layers (DeepSeek's first_k_dense_replace); num_layers
    # counts them
    first_dense_layers: int = 0

    # attention
    rope_theta: float = 10_000.0
    sliding_window: int = 4096
    parallel_block: bool = False       # command-r style attn ∥ ffn
    use_bias: bool = False

    # latent attention ("mla"): K and V from a compressed latent of
    # kv_latent_dim (DeepSeek's kv_lora_rank) with its own RMSNorm; each
    # head scores over qk_nope_head_dim + qk_rope_head_dim dims, the
    # rotary key one head shared by all heads
    kv_latent_dim: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN rotary scaling; factor 1 is plain RoPE
    rope_scaling_factor: float = 1.0
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0

    # MoE
    num_experts: int = 0
    experts_per_token: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int = 0                  # per-expert hidden dim (0 -> d_ff)
    # per-expert cap on the sorted rows; None routes every token (dropless)
    capacity_factor: Optional[float] = 1.25
    router_aux_coef: float = 0.01
    # "switch": coef * E * sum_e mean(p_e) * top-1 share of e, over the
    # batch; "seq": DeepSeek's sequence-wise loss, coef * mean over
    # sequences of sum_e f_e * P_e, f_e = E / (K T) * assignments to e
    router_aux: str = "switch"
    norm_topk_prob: bool = True        # renormalize the top-k gates
    routed_scaling_factor: float = 1.0
    # the layer routes over all num_experts and holds experts
    # [expert_offset, expert_offset + experts_held); 0 holds all
    experts_held: int = 0
    expert_offset: int = 0
    # dtype of the operands of the expert products (float32 accumulation):
    # float32 for the presets whose tests hold them to a float32
    # reference; deepseek-v2-lite sets bfloat16, as its benchmark cell runs
    expert_operand_dtype: str = "float32"

    # SSM (Mamba)
    ssm_state_dim: int = 16
    ssm_conv_width: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: int = 0               # 0 -> ceil(d_model / 16)

    # frontend: tokens (LM) | embeds (audio/vlm stub — precomputed
    # frame/patch embeddings of shape (B, S, d_model))
    frontend: str = "tokens"

    # adaptive layer-wise density (core/adaptk.py, DESIGN.md §9):
    # "" = fixed-k; "uniform" | "variance" | "absmax" is the default
    # --density-policy the training CLI resolves for this arch
    density_policy: str = ""

    # numerics
    param_dtype: str = "float32"
    activation_dtype: str = "float32"
    # perf knob (§Perf): pin the residual-stream scan carry sharded over
    # 'model' — 16x smaller activation stacks for the backward pass at the
    # cost of per-layer all-gathers
    shard_activations: bool = False

    # citation / provenance
    source: str = ""

    # ------------------------------------------------------------------
    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.num_heads

    @property
    def dt_rank(self) -> int:
        return self.ssm_dt_rank if self.ssm_dt_rank else max(1, -(-self.d_model // 16))

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    def block_kind(self, layer: int) -> str:
        return self.block_pattern[layer % len(self.block_pattern)]

    def ffn_kind(self, layer: int) -> str:
        return self.ffn_pattern[layer % len(self.ffn_pattern)]

    @property
    def pattern_period(self) -> int:
        import math
        return abs(math.lcm(len(self.block_pattern), len(self.ffn_pattern)))

    def layer_sig(self, layer: int) -> Tuple[str, str]:
        return (self.block_kind(layer), self.ffn_kind(layer))

    def expert_d_ff(self) -> int:
        return self.moe_d_ff if self.moe_d_ff else self.d_ff

    @property
    def held_experts(self) -> int:
        return self.experts_held if self.experts_held else self.num_experts

    @property
    def mla_q_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def validate(self) -> "ModelConfig":
        assert self.d_model % self.num_heads == 0 or self.head_dim, self.name
        assert self.num_heads % self.num_kv_heads == 0, self.name
        if "moe" in self.ffn_pattern:
            assert self.num_experts > 0 and self.experts_per_token > 0, self.name
            assert 0 <= self.expert_offset, self.name
            assert (self.expert_offset + self.held_experts
                    <= self.num_experts), self.name
            assert self.router_aux in ("switch", "seq"), self.name
        if "mla" in self.block_pattern:
            assert min(self.kv_latent_dim, self.qk_nope_head_dim,
                       self.qk_rope_head_dim, self.v_head_dim) > 0, self.name
            assert self.qk_rope_head_dim % 2 == 0, self.name
            assert self.num_kv_heads == self.num_heads, self.name
        assert self.rope_scaling_factor >= 1.0, self.name
        assert 0 <= self.first_dense_layers <= self.num_layers, self.name
        assert self.frontend in ("tokens", "embeds"), self.name
        return self

    def reduced(self, **over) -> "ModelConfig":
        """Smoke-test variant of the same family: ≤2 layers, d_model ≤ 512,
        ≤4 experts (assignment requirement)."""
        period = self.pattern_period
        layers = min(2 * period, max(period, 2))
        hd = 64 if self.hd >= 64 else self.hd
        heads = max(2, min(4, self.num_heads))
        kv = max(1, min(heads, self.num_kv_heads))
        while heads % kv:
            kv -= 1
        small = dict(
            name=self.name + "-smoke",
            num_layers=layers,
            d_model=min(self.d_model, 256),
            num_heads=heads,
            num_kv_heads=kv,
            head_dim=hd if self.head_dim else None,
            d_ff=min(self.d_ff, 512) if self.d_ff else 0,
            vocab_size=min(self.vocab_size, 512),
            num_experts=min(self.num_experts, 4) if self.num_experts else 0,
            experts_per_token=min(self.experts_per_token, 2)
            if self.experts_per_token else 0,
            num_shared_experts=min(self.num_shared_experts, 1)
            if self.num_shared_experts else 0,
            moe_d_ff=min(self.moe_d_ff, 256) if self.moe_d_ff else 0,
            sliding_window=128,
            first_dense_layers=min(self.first_dense_layers, 1),
            kv_latent_dim=min(self.kv_latent_dim, 64),
            qk_nope_head_dim=min(self.qk_nope_head_dim, 32),
            qk_rope_head_dim=min(self.qk_rope_head_dim, 16),
            v_head_dim=min(self.v_head_dim, 32),
        )
        if self.experts_held:
            small["experts_held"] = min(self.experts_held,
                                        small["num_experts"])
            small["expert_offset"] = 0
        hd2 = small["d_model"] // small["num_heads"]
        if small["head_dim"] is not None:
            small["head_dim"] = hd2
        small.update(over)
        return dataclasses.replace(self, **small).validate()
