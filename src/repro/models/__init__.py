"""JAX model zoo: dense GQA, MoE, latent attention, Mamba-2 SSD, RG-LRU
hybrid, Kimi Delta Attention hybrid, enc-dec, VLM/audio backbones —
metadata-first params, scan-over-layers stacks."""

from repro.models.model import (param_shapes, init_params, abstract_params,
                                forward, loss_fn, cache_shapes, init_cache,
                                abstract_cache, decode_step, prefill)

__all__ = ["param_shapes", "init_params", "abstract_params", "forward",
           "loss_fn", "cache_shapes", "init_cache", "abstract_cache",
           "decode_step", "prefill"]
