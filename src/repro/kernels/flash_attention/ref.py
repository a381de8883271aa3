"""Pure-jnp oracle for flash attention (causal / sliding-window / full, GQA).

This is the correctness reference every kernel test asserts against, and the
default model path on CPU (XLA fuses it; the Pallas kernel targets TPU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["attention_ref", "attention_chunked"]

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


def attention_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: int | None = None,
                      scale: float | None = None, q_block: int = 1024,
                      k_block: int = 1024) -> jax.Array:
    """Blockwise online-softmax attention (flash attention expressed in XLA).

    Never materialises the (Sq, Sk) score matrix: the KV axis is consumed by
    a rematerialised ``lax.scan`` carrying the running (max, sum, acc)
    triple, so peak bytes are O(S·D) instead of O(S²) — the memory-roofline
    fix for long-sequence training on TPU (§Perf, llama3-405b train_4k).
    Causality is honoured structurally: q-block i only scans k-blocks
    ≤ its diagonal (a python loop — block count is static), so FLOPs stay
    ~triangular rather than doubling.

    Shapes as :func:`attention_ref`. Numerics: softmax in float32.
    """
    B, Sq, H, D = q.shape
    _, Sk, K, _ = k.shape
    assert H % K == 0, (H, K)
    G = H // K
    scale = D ** -0.5 if scale is None else scale
    q_block = min(q_block, Sq)
    k_block = min(k_block, Sk)
    if Sq % q_block or Sk % k_block:
        return attention_ref(q, k, v, causal=causal, window=window,
                             scale=scale)
    nq = Sq // q_block

    def one_qblock(args, lo: int, hi: int, q0: int):
        """Scan k-blocks [lo, hi) for one q block starting at position q0."""
        qb, = args
        qf = qb.astype(jnp.float32).reshape(B, q_block, K, G, D) * scale
        nk = (hi - lo) // k_block
        qpos = q0 + jnp.arange(q_block)

        def body(carry, j):
            acc, m, l = carry
            start = lo + j * k_block
            kb = jax.lax.dynamic_slice_in_dim(k, start, k_block, 1)
            vb = jax.lax.dynamic_slice_in_dim(v, start, k_block, 1)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qf,
                           kb.astype(jnp.float32))          # (B,K,G,qb,kb)
            kpos = start + jnp.arange(k_block)
            mask = jnp.ones((q_block, k_block), bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask &= qpos[:, None] - kpos[None, :] < window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p, vb.astype(jnp.float32))
            return (acc_new, m_new, l_new), None

        init = (jnp.zeros((B, K, G, q_block, v.shape[-1]), jnp.float32),
                jnp.full((B, K, G, q_block), -jnp.inf, jnp.float32),
                jnp.zeros((B, K, G, q_block), jnp.float32))
        (acc, m, l), _ = jax.lax.scan(jax.checkpoint(body), init,
                                      jnp.arange(nk))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return jnp.moveaxis(out, 3, 1).reshape(B, q_block, H, v.shape[-1])

    outs = []
    for i in range(nq):
        q0 = i * q_block
        qb = jax.lax.slice_in_dim(q, q0, q0 + q_block, axis=1)
        if causal:
            # decode-style offset: the last q row sits at absolute position
            # Sk - Sq + q0 + q_block - 1
            hi = min(Sk, Sk - Sq + q0 + q_block)
            hi = ((hi + k_block - 1) // k_block) * k_block
            hi = min(hi, Sk)
        else:
            hi = Sk
        lo = 0
        if window is not None:
            lo = max(0, (Sk - Sq + q0) - window + 1)
            lo = (lo // k_block) * k_block
        outs.append(one_qblock((qb,), lo, hi, Sk - Sq + q0))
    return jnp.concatenate(outs, axis=1).astype(q.dtype)
