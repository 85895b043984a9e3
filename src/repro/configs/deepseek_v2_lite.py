"""deepseek-v2-lite — latent attention (no query compression, YaRN
rotary), one leading dense layer, then 26 expert layers of 64 routed
experts (top-6 by softmax score, gates not renormalized) and 2 shared.
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite", arch_type="moe",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=10944, vocab_size=102400, rope_theta=10_000.0,
    block_pattern=("mla",), ffn_pattern=("moe",), first_dense_layers=1,
    kv_latent_dim=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, rope_scaling_factor=40.0,
    rope_original_max_position=4096, rope_beta_fast=32.0,
    rope_beta_slow=1.0, rope_mscale=0.707, rope_mscale_all_dim=0.707,
    num_experts=64, experts_per_token=6, num_shared_experts=2,
    moe_d_ff=1408, capacity_factor=None, router_aux="seq",
    router_aux_coef=0.001, norm_topk_prob=False, routed_scaling_factor=1.0,
    expert_operand_dtype="bfloat16",
    source="hf:deepseek-ai/DeepSeek-V2-Lite",
).validate()
