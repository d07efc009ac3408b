"""moonlight-16b-a3b [moe] — DeepSeek-V3 blocks: latent attention (MLA, no
q LoRA), 64 sigmoid-routed experts top-6 with a selection bias, 2 shared
experts, one leading dense layer [hf:moonshotai/Moonlight-16B-A3B].

Served as one chip's share of an expert-parallel deployment over eight
chips: every MoE layer holds experts 0-7 of the router's 64."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=1,
    d_ff=11264, vocab_size=163840, rope_theta=50_000.0,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    n_experts=64, moe_top_k=6, moe_d_ff=1408, n_shared_experts=2,
    experts_held=8, expert_offset=0, moe_scale=2.446, first_dense=1,
)
