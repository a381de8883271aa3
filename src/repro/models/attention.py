"""Attention blocks: GQA full/causal/sliding-window + decode-step paths.

Layout convention: activations (B, S, D); projections keep heads explicit
((B, S, H, Dh)) so the `heads` logical axis shards over the mesh `model`
axis without reshapes. KV caches are head-major, (B, K, Smax, Dh): the
layout the decode's score and value dots read, so a layer's cache feeds them
straight from a layer-stacked buffer, with no transposed copy. Sliding-window
archs use a ring buffer of size ``window`` so a 500k-token decode holds a
bounded cache (the systems point that makes `long_500k` runnable at all).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention
from repro.models.layers import ParamSpec, apply_rope, rms_norm, rotary_embedding

__all__ = ["attn_specs", "attn_apply", "attn_decode", "cross_attn_apply"]


def attn_specs(cfg, *, cross: bool = False) -> dict:
    D, H, K, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s = {
        "wq": ParamSpec((D, H, Dh), ("embed", "heads", "head")),
        "wk": ParamSpec((D, K, Dh), ("embed", "kv_heads", "head")),
        "wv": ParamSpec((D, K, Dh), ("embed", "kv_heads", "head")),
        "wo": ParamSpec((H, Dh, D), ("heads", "head", "embed"),
                        fan_in_axes=(0, 1)),
    }
    if cfg.qkv_bias and not cross:
        s["bq"] = ParamSpec((H, Dh), ("heads", "head"), init="zeros")
        s["bk"] = ParamSpec((K, Dh), ("kv_heads", "head"), init="zeros")
        s["bv"] = ParamSpec((K, Dh), ("kv_heads", "head"), init="zeros")
    return s


def _qkv(p, x, xkv, cfg):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", xkv, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", xkv, p["wv"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    return q, k, v


def attn_apply(p: dict, x: jax.Array, cfg, *, causal: bool = True,
               window: int | None = None, positions: jax.Array | None = None,
               return_kv: bool = False):
    """Full-sequence (train / prefill) self-attention. x: (B, S, D)."""
    B, S, D = x.shape
    q, k, v = _qkv(p, x, x, cfg)
    if positions is None:
        positions = jnp.arange(S)[None, :]
    sin, cos = rotary_embedding(positions, cfg.head_dim, cfg.rope_theta)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    o = flash_attention(q, k, v, causal=causal, window=window,
                        use_pallas=cfg.use_pallas, chunked=cfg.attn_chunked,
                        q_chunk=cfg.attn_q_block, k_chunk=cfg.attn_k_block)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    if return_kv:
        return out, (k, v)
    return out


def cross_memory_kv(p: dict, enc_out: jax.Array):
    """Per-layer cross-attention K/V over encoder output (no rope)."""
    mk = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(enc_out.dtype))
    mv = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(enc_out.dtype))
    return mk, mv


def cross_attn_apply(p: dict, x: jax.Array, memory, cfg):
    """Decoder cross-attention. ``memory`` is either the encoder output
    (B, F, D) — K/V computed here — or a precomputed (mk, mv) cache."""
    mk, mv = memory if isinstance(memory, tuple) else cross_memory_kv(p, memory)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    o = flash_attention(q, mk.astype(x.dtype), mv.astype(x.dtype),
                        causal=False, use_pallas=cfg.use_pallas)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))


def attn_decode(p: dict, x: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                pos: jax.Array, cfg, *, window: int | None = None,
                layer: jax.Array | None = None):
    """One-token decode step.

    x: (B, 1, D); cache_k/v: (B, K, Smax, Dh), or (L, B, K, Smax, Dh) stacked
    over layers with ``layer`` this layer's index; pos: (B,) int32 (absolute
    position of each row's token — rows may differ under continuous
    batching). Sliding-window caches (Smax == window) are ring buffers
    indexed ``pos % Smax``; rope uses absolute positions so rotation is
    consistent across wraps. Only each row's new key and value are written,
    at ``[layer,] row, :, pos % Smax``, so a donated cache is updated in place;
    the attention then reads this layer's cache with the new rows in it.
    Returns (out (B,1,D), cache_k, cache_v), the caches as passed in plus
    those rows.
    """
    B, _, D = x.shape
    K, Smax = cache_k.shape[-3], cache_k.shape[-2]
    H, Dh = cfg.num_heads, cfg.head_dim
    G = H // K
    pos = jnp.broadcast_to(pos, (B,)).astype(jnp.int32)

    q, k_new, v_new = _qkv(p, x, x, cfg)
    sin, cos = rotary_embedding(pos[:, None], cfg.head_dim, cfg.rope_theta)
    q = apply_rope(q, sin, cos)
    k_new = apply_rope(k_new, sin, cos)

    slot = (pos % Smax).astype(jnp.int32)                 # (B,)
    # one index per (row, head): each write is one (Dh,) vector, a form the
    # TPU scatters without changing the cache's layout
    at = (jnp.arange(B)[:, None], jnp.arange(K)[None, :], slot[:, None])
    if layer is not None:
        at = (layer, *at)
    cache_k = cache_k.at[at].set(k_new[:, 0].astype(cache_k.dtype))
    cache_v = cache_v.at[at].set(v_new[:, 0].astype(cache_v.dtype))
    layer_k = cache_k if layer is None else cache_k[layer]
    layer_v = cache_v if layer is None else cache_v[layer]

    qf = q.astype(jnp.float32).reshape(B, K, G, Dh)
    kf = layer_k.astype(jnp.float32)
    vf = layer_v.astype(jnp.float32)
    s = jnp.einsum("bkgd,bktd->bkgt", qf, kf) * (Dh ** -0.5)
    # slot j holds the token `age = (slot - j) mod Smax` steps in the past
    idx = jnp.arange(Smax)[None, :]
    age = (slot[:, None] - idx) % Smax                    # (B, Smax); 0 = now
    valid = age <= jnp.minimum(pos, Smax - 1)[:, None]    # written yet?
    if window is not None:
        valid &= age < window
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    pattn = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,bktd->bkgd", pattn, vf).reshape(B, 1, H, Dh)
    out = jnp.einsum("bshk,hkd->bsd", o.astype(x.dtype), p["wo"].astype(x.dtype))
    return out, cache_k, cache_v
