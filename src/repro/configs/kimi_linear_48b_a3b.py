"""kimi-linear-48b-a3b — Kimi-Linear-48B-A3B: 27 layers, Kimi Delta
Attention (gated delta-rule linear attention, 32 heads of 128, short conv
4) in 20 of them and latent attention without rotary embedding (MLA, q
from x, latent 512, q·k 128 + 64, v 128) in the other 7, in the pattern
KDA, KDA, KDA, MLA; a leading dense layer of 9216, then 256-expert top-8
MoE of 1024 with one shared expert, sigmoid routing with a choice bias,
gates renormalised and scaled by 2.446
[hf:moonshotai/Kimi-Linear-48B-A3B-Instruct]."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="kimi-linear-48b-a3b", family="hybrid",
    num_layers=27, d_model=2304, num_heads=32, num_kv_heads=32,
    d_ff=9216, vocab_size=163840, head_dim=192,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, mla_nope=True,
    kda_layers=(1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22,
                23, 25, 26),
    kda_heads=32, kda_head_dim=128, conv_width=4,
    num_experts=256, num_experts_per_tok=8, moe_d_ff=1024,
    num_shared_experts=1, first_dense_layers=1, router="sigmoid",
    routed_scaling=2.446, rope_theta=10000.0, norm_eps=1e-5,
)

SMOKE = CONFIG.replace(
    name="kimi-linear-smoke", num_layers=4, d_model=64, num_heads=4,
    num_kv_heads=4, head_dim=24, d_ff=128, vocab_size=256,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    kda_heads=4, kda_head_dim=16,
    num_experts=8, experts_held=4, num_experts_per_tok=2, moe_d_ff=32,
)
