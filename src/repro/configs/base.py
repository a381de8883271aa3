"""Model/run configuration system.

A config is a frozen dataclass; every assigned architecture contributes one
module in this package exposing ``CONFIG`` (the published widths) and
``SMOKE`` (reduced same-family config runnable on CPU). ``repro.configs.get``
resolves ``--arch`` flags.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field

__all__ = ["ModelConfig"]


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0               # 0 → d_model // num_heads

    # attention
    attention: str = "full"         # full | swa | none
    window: int = 4096              # sliding window (attention == "swa" / local)
    qkv_bias: bool = False

    # latent attention (MLA, DeepSeek-V2/V3): keys and values expand from
    # one normed latent of kv_lora_rank per token, with a rope part of
    # qk_rope_head_dim shared by every head; the decode caches the latent
    kv_lora_rank: int = 0           # 0 → no latent attention
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # MoE
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_d_ff: int = 0
    num_shared_experts: int = 0     # always-on SwiGLU of that many × moe_d_ff
    first_dense_layers: int = 0     # leading layers with a dense d_ff MLP
    router: str = "softmax"         # softmax | sigmoid (choice bias, noaux_tc)
    routed_scaling: float = 1.0     # gates × this after their normalisation
    capacity_factor: float = 1.25   # training dispatch only; serving drops none
    router_aux_coef: float = 0.01

    # SSM (mamba2 / SSD)
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    conv_width: int = 4

    # hybrid (recurrentgemma): repeating block pattern
    block_pattern: tuple = ()       # e.g. ("rglru", "rglru", "local_attn")
    lru_width: int = 0

    # encoder-decoder (seamless)
    encoder_layers: int = 0
    cross_attention: bool = False

    # modality frontend stub (vlm/audio): precomputed embeddings prepended
    frontend: str = "none"          # none | vision_stub | audio_stub
    frontend_tokens: int = 0

    # misc
    mlp_variant: str = "swiglu"     # swiglu | gelu (non-gated)
    rope_theta: float = 1e4
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.head_dim == 0 and self.num_heads:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        if self.family == "ssm" and self.ssm_heads == 0:
            object.__setattr__(
                self, "ssm_heads",
                (self.d_model * self.ssm_expand) // self.ssm_head_dim)

    # ------------------------------------------------------------ derived
    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # parameter count (for 6ND model-flops accounting)
    def _flat_param_specs(self):
        import jax
        from repro.models.model import param_shapes
        from repro.models.layers import ParamSpec
        flat = jax.tree_util.tree_flatten_with_path(
            param_shapes(self), is_leaf=lambda x: isinstance(x, ParamSpec))[0]
        return [(jax.tree_util.keystr(path), spec) for path, spec in flat]

    def param_count(self) -> int:
        import math
        return sum(math.prod(s.shape) if s.shape else 1
                   for _, s in self._flat_param_specs())

    def active_param_count(self) -> int:
        """MoE: params touched per token (top-k of E experts)."""
        if self.num_experts == 0:
            return self.param_count()
        import math
        total = 0
        for path, spec in self._flat_param_specs():
            n = math.prod(spec.shape) if spec.shape else 1
            if "we_" in path or "experts" in path:
                n = n * self.num_experts_per_tok // self.num_experts
            total += n
        return total

