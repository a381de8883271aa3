"""moonshot-v1-16b-a3b — Moonlight-16B-A3B (DeepSeek-V3 block): latent
attention (MLA, q projected straight from x, latent 512, q·k 128 + 64 rope,
v 128), one leading dense layer, then 64-expert top-6 fine-grained MoE with
two shared experts and sigmoid routing with a choice bias (noaux_tc), gates
normalised and scaled by 2.446 [hf:moonshotai/Moonlight-16B-A3B]."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="moonshot-v1-16b-a3b", family="moe",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=11264, vocab_size=163840, head_dim=192,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    num_experts=64, num_experts_per_tok=6, moe_d_ff=1408,
    num_shared_experts=2, first_dense_layers=1, router="sigmoid",
    routed_scaling=2.446, rope_theta=50000.0, norm_eps=1e-5,
)

SMOKE = CONFIG.replace(
    name="moonshot-smoke", num_layers=3, d_model=64, num_heads=4,
    num_kv_heads=4, head_dim=24, d_ff=128, vocab_size=256,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    num_experts=8, num_experts_per_tok=2, moe_d_ff=32,
)
