"""Pure-jnp oracle for flash attention (causal / sliding-window / full, GQA).

This is the correctness reference every kernel test asserts against, and the
model's full-sequence path (training, prefill) on every platform: only the
kernel tests and ``chip_smoke.py`` run the Pallas kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["attention_ref"]

NEG_INF = -1e30


def _mask(sq: int, sk: int, *, causal: bool, window: int | None,
          q_offset: int) -> jax.Array:
    """(sq, sk) boolean mask; True = attend. q position i sits at absolute
    position q_offset + i; k position j at absolute j."""
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(sk)[None, :]
    m = jnp.ones((sq, sk), bool)
    if causal:
        m &= kpos <= qpos
    if window is not None:
        m &= qpos - kpos < window
    return m


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, window: int | None = None,
                  scale: float | None = None, q_offset: int = 0) -> jax.Array:
    """Grouped-query attention.

    q, k: (B, Sq|Sk, H|K, D); v: (B, Sk, K, Dv) with H % K == 0.
    Returns (B, Sq, H, Dv) in q.dtype; softmax in float32.
    """
    B, Sq, H, D = q.shape
    _, Sk, K, _ = k.shape
    assert H % K == 0, (H, K)
    G = H // K
    scale = D ** -0.5 if scale is None else scale
    qf = q.astype(jnp.float32).reshape(B, Sq, K, G, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qf, kf) * scale
    m = _mask(Sq, Sk, causal=causal, window=window, q_offset=q_offset)
    s = jnp.where(m[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, vf)
    return o.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)

