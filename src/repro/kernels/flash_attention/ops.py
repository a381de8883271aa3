"""jit'd public wrapper for flash attention.

Model code calls :func:`flash_attention` with (B, S, H, D)-layout tensors
(the model's native layout); this wrapper transposes to the kernel's
(B, H, S, D) tiling layout, dispatches to the Pallas kernel (interpret mode
on CPU, compiled on TPU) or to the pure-jnp oracle, and transposes back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.flash_attention.ref import attention_ref

__all__ = ["flash_attention"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "use_pallas", "block_q",
                                             "block_k"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None, use_pallas: bool = False,
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    """GQA attention. q: (B, Sq, H, D); k, v: (B, Sk, K, D) → (B, Sq, H, D)."""
    if not use_pallas:
        return attention_ref(q, k, v, causal=causal, window=window, scale=scale)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    ot = flash_attention_kernel(qt, kt, vt, causal=causal, window=window,
                                scale=scale, block_q=block_q, block_k=block_k,
                                interpret=not _on_tpu())
    return jnp.swapaxes(ot, 1, 2)
