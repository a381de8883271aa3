"""Attention blocks: GQA full/causal/sliding-window + decode-step paths.

Layout convention: activations (B, S, D); projections keep heads explicit
((B, S, H, Dh)) so the `heads` logical axis shards over the mesh `model`
axis without reshapes. KV caches are head-major, (B, K, Smax, Dh): the
layout the decode's score and value dots read, so a layer's cache feeds them
straight from a layer-stacked buffer, with no transposed copy. Sliding-window
archs use a ring buffer of size ``window``, so a decode of any length holds
a bounded cache.

Latent attention (MLA) caches one normed latent row per token, shared by
every head: (B, Smax, kv_lora_rank + qk_rope_head_dim), the latent followed
by the roped key part. Its prefill expands the latent to per-head keys and
values and runs the usual attention; its decode never expands the cache:
the key up-projection is absorbed into the query and the value
up-projection into the output, so the scores and the weighted sum read the
latent rows directly.

Decode attention reads a cache one of two ways, chosen by the platform of
the devices the step is compiled for: on TPUs the ragged Pallas kernel
(``kernels/decode_attention``), which reads only each row's live slots
(per shard of the cache on a mesh); elsewhere the jnp path, which reads a
layer's whole cache and masks it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import (decode_attention,
                                            grouped_decode_ref,
                                            latent_decode_ref)
from repro.kernels.flash_attention import flash_attention
from repro.models.layers import ParamSpec, apply_rope, rms_norm, rotary_embedding
from repro.parallel import ctx
from repro.parallel import sharding as shd

__all__ = ["attn_specs", "attn_apply", "attn_decode", "cross_attn_apply",
           "mla_specs", "mla_apply", "mla_decode"]


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
    o = flash_attention(q, k, v, causal=causal, window=window)
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
                        causal=False)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))


def _kernel_target():
    """(mesh, rules) of the step being traced where its devices are TPUs,
    so decode attention runs the ragged kernel; None for the jnp path."""
    target = ctx.current()
    if target is not None and target[0].devices.flat[0].platform == "tpu":
        return target
    return None


def _ragged(target, q, k, v, pos, layer, cfg, *, scale: float,
            value_width: int | None = None):
    """The ragged kernel on ``target``'s mesh, over the cache's own
    sharding (``v`` None: the latent rows are the values)."""
    mesh, rules = target
    stack = k.shape if layer is not None else (1, *k.shape)
    spec = shd.cache_leaf_pspec(stack, bdim=1, head_major=v is not None,
                                rules=rules, mesh=mesh, cfg=cfg)
    return decode_attention(q, k, v, pos, layer, mesh=mesh, cache_spec=spec,
                            scale=scale, value_width=value_width)


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

    q = q.reshape(B, K, G, Dh)
    target = _kernel_target()
    if target is None:
        o = grouped_decode_ref(q, cache_k, cache_v, pos, layer=layer,
                               window=window)
    else:
        # a ring buffer is never longer than its window: its live slots
        # are then the first min(pos + 1, Smax)
        assert window is None or Smax <= window, (Smax, window)
        o = _ragged(target, q, cache_k, cache_v, pos, layer, cfg,
                    scale=Dh ** -0.5)
    o = o.reshape(B, 1, H, Dh)
    out = jnp.einsum("bshk,hkd->bsd", o.astype(x.dtype), p["wo"].astype(x.dtype))
    return out, cache_k, cache_v


# ------------------------------------------------------- latent attention
def mla_specs(cfg) -> dict:
    D, H = cfg.d_model, cfg.num_heads
    N, R, C = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    V = cfg.v_head_dim
    return {
        "wq": ParamSpec((D, H, N + R), ("embed", "heads", "head")),
        "wkv_a": ParamSpec((D, C + R), ("embed", None)),
        "kv_norm": ParamSpec((C,), (None,), init="ones"),
        "wk_b": ParamSpec((C, H, N), (None, "heads", "head")),
        "wv_b": ParamSpec((C, H, V), (None, "heads", "head")),
        "wo": ParamSpec((H, V, D), ("heads", "head", "embed"),
                        fan_in_axes=(0, 1)),
    }


def _rope_pairs(x, positions, cfg):
    """Rotary embedding of adjacent pairs (2i, 2i+1) at frequency i, as the
    published MLA models apply it: the pairs are gathered into halves, then
    rotated. x: (B, S, heads, R); positions: (B, S)."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    sin, cos = rotary_embedding(positions, cfg.qk_rope_head_dim, cfg.rope_theta)
    return apply_rope(x, sin, cos)


def _mla_project(p, x, positions, cfg):
    """(q_nope (B,S,H,N), q_pe (B,S,H,R) roped, the latent row (B,S,C+R):
    the normed latent then the roped key part)."""
    N, C = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    kv = jnp.einsum("bsd,dc->bsc", x, p["wkv_a"].astype(x.dtype))
    c = rms_norm(kv[..., :C], p["kv_norm"], cfg.norm_eps)
    k_pe = _rope_pairs(kv[..., None, C:], positions, cfg)[..., 0, :]
    return q[..., :N], _rope_pairs(q[..., N:], positions, cfg), \
        jnp.concatenate([c, k_pe], axis=-1)


def mla_apply(p: dict, x: jax.Array, cfg):
    """Full-sequence causal latent attention (train / prefill), with the
    latent expanded to per-head keys (N + R wide) and values (V wide).
    x: (B, S, D). Returns (out (B, S, D), latent rows (B, S, C + R))."""
    B, S, _ = x.shape
    H, C = cfg.num_heads, cfg.kv_lora_rank
    with jax.named_scope("mla.latent"):
        q_nope, q_pe, row = _mla_project(p, x, jnp.arange(S)[None, :], cfg)
        c = row[..., :C]
        k_nope = jnp.einsum("bsc,chn->bshn", c, p["wk_b"].astype(x.dtype))
        v = jnp.einsum("bsc,chv->bshv", c, p["wv_b"].astype(x.dtype))
        k_pe = jnp.broadcast_to(row[:, :, None, C:],
                                (B, S, H, cfg.qk_rope_head_dim))
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        k = jnp.concatenate([k_nope, k_pe], axis=-1)
    with jax.named_scope("mla.attend"):
        o = flash_attention(q, k, v, causal=True)
        out = jnp.einsum("bshv,hvd->bsd", o, p["wo"].astype(x.dtype))
    return out, row


def mla_decode(p: dict, x: jax.Array, cache: jax.Array, pos: jax.Array, cfg,
               *, layer: jax.Array | None = None):
    """One-token latent-attention step over the latent cache, absorbed.

    x: (B, 1, D); cache: (B, Smax, C + R), or (L, B, Smax, C + R) stacked
    over layers with ``layer`` this layer's index; pos: (B,) int32. Each
    row's new latent row is written at ``[layer,] row, pos % Smax`` (in
    place in a donated cache). The scores are ``q_nope·W_kb`` (per head,
    C wide) against the latent plus ``q_pe`` against the roped key part;
    the weighted sum of latent rows goes through ``W_vb`` per head, then
    ``wo``. The dots take the cache's dtype with float32 sums. Returns
    (out (B, 1, D), cache).
    """
    B = x.shape[0]
    C = cfg.kv_lora_rank
    Smax = cache.shape[-2]
    pos = jnp.broadcast_to(pos, (B,)).astype(jnp.int32)
    with jax.named_scope("mla.latent"):
        q_nope, q_pe, row = _mla_project(p, x, pos[:, None], cfg)
        slot = (pos % Smax).astype(jnp.int32)
        # written as (C + R) / w pieces of w: a whole 576-wide row per index
        # makes the TPU compiler relayout the whole cache around the scatter
        w = math.gcd(C, cfg.qk_rope_head_dim)
        pieces = cache.reshape(*cache.shape[:-1], -1, w)
        n = pieces.shape[-2]
        at = (jnp.arange(B)[:, None], slot[:, None], jnp.arange(n)[None, :])
        if layer is not None:
            at = (layer, *at)
        pieces = pieces.at[at].set(row[:, 0].reshape(B, n, w).astype(cache.dtype))
        cache = pieces.reshape(cache.shape)
        q_lat = jnp.einsum("bhn,chn->bhc", q_nope[:, 0],
                           p["wk_b"].astype(x.dtype))
        qc = jnp.concatenate([q_lat, q_pe[:, 0]], axis=-1).astype(cache.dtype)
    with jax.named_scope("mla.attend"):
        scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
        target = _kernel_target()
        if target is None:
            o_lat = latent_decode_ref(qc, cache, pos, scale=scale,
                                      value_width=C, layer=layer)
        else:
            o_lat = _ragged(target, qc[:, None], cache, None, pos, layer,
                            cfg, scale=scale, value_width=C)[:, 0]
        o = jnp.einsum("bhc,chv->bhv", o_lat.astype(x.dtype),
                       p["wv_b"].astype(x.dtype))
        out = jnp.einsum("bhv,hvd->bd", o, p["wo"].astype(x.dtype))
    return out[:, None], cache
