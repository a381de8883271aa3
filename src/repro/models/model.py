"""Model assembly: param shapes, forward/loss, prefill and decode steps for
every assigned architecture family.

A stack whose layer kinds, after the leading dense layers, are all one kind
is stacked and scanned (depth-independent HLO ⇒ fast compiles); any other
pattern (recurrentgemma's) unrolls, one dict per layer. Each layer body is
rematerialised. A mixture-of-experts model's leading dense layers
(``first_dense_layers``) form a group of their own, ``dense_layers``,
stacked like ``layers`` but applied unrolled ahead of the scanned MoE
layers. In the params and the cache a group is either stacked
({group: {leaf: (L, ...)}}) or holds one dict per layer
({group: {"layer_i": {leaf: ...}}}); :func:`stacked` tells a cache's apart
by that structure.
All public functions treat ``cfg`` as static (hashable frozen dataclass).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import moe as moe_mod
from repro.models import transformer as tfm
from repro.models.layers import (ParamSpec, abstract_tree, init_tree,
                                 rms_norm, stacked, take_embedding)
from repro.models.ssm import ssm_cache_shapes
from repro.parallel.ctx import constrain_logical
from repro.models.rglru import rglru_cache_shapes

__all__ = ["param_shapes", "init_params", "abstract_params", "forward",
           "loss_fn", "cache_shapes", "init_cache", "abstract_cache",
           "decode_step", "prefill", "compute_dtype", "stacked"]


def compute_dtype(cfg):
    return jnp.dtype(cfg.dtype)


def _uniform_scan(cfg) -> bool:
    """Stacked and scanned: the layers after the leading ones are one kind."""
    return len(set(tfm.layer_kinds(cfg)[cfg.first_dense_layers:])) == 1


def _layer(tree, i: int):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


# --------------------------------------------------------------------- specs
def param_shapes(cfg) -> dict:
    kinds = tfm.layer_kinds(cfg)
    D, V = cfg.d_model, cfg.vocab_size
    specs: dict[str, Any] = {
        "embed": ParamSpec((V, D), ("vocab", "embed"), init="embed"),
        "final_norm": ParamSpec((D,), ("embed",), init="ones"),
    }
    if not cfg.tie_embeddings:
        specs["unembed"] = ParamSpec((D, V), ("embed", "vocab"))
    if _uniform_scan(cfg):
        lead = cfg.first_dense_layers
        groups = {"dense_layers": (kinds[0], lead),
                  "layers": (kinds[-1], cfg.num_layers - lead)}
        for group, (kind, n) in groups.items():
            if n:
                specs[group] = jax.tree_util.tree_map(
                    lambda s: s.with_prefix(n), tfm.block_specs(cfg, kind),
                    is_leaf=lambda x: isinstance(x, ParamSpec))
    else:
        specs["layers"] = {f"layer_{i}": tfm.block_specs(cfg, k)
                           for i, k in enumerate(kinds)}
    if cfg.is_encdec:
        enc_block = tfm.block_specs(cfg, "enc_attn")
        specs["encoder"] = {
            "layers": jax.tree_util.tree_map(
                lambda s: s.with_prefix(cfg.encoder_layers), enc_block,
                is_leaf=lambda x: isinstance(x, ParamSpec)),
            "final_norm": ParamSpec((D,), ("embed",), init="ones"),
        }
    return specs


def init_params(cfg, rng, dtype=jnp.float32):
    return init_tree(param_shapes(cfg), rng, dtype)


def abstract_params(cfg, dtype=jnp.float32):
    return abstract_tree(param_shapes(cfg), dtype)


# -------------------------------------------------------------------- trunk
def _stack_apply(params, x, cfg, kinds, *, memory=None):
    """Run the layer stack. Returns (x, aux)."""
    layers_p = params["layers"]
    if _uniform_scan(cfg):
        for i in range(cfg.first_dense_layers):
            x, _ = tfm.block_apply(_layer(params["dense_layers"], i), x, cfg,
                                   kinds[i])
        kind = kinds[-1]

        def body(carry, layer_p):
            h, aux = carry
            h, a = tfm.block_apply(layer_p, h, cfg, kind, memory=memory)
            return (h, aux + a), None

        (x, aux), _ = jax.lax.scan(jax.checkpoint(body),
                                   (x, jnp.float32(0.0)), layers_p)
        return x, aux
    aux = jnp.float32(0.0)
    for i, kind in enumerate(kinds):
        blk = jax.checkpoint(
            functools.partial(tfm.block_apply, kind=kind, memory=memory),
            static_argnums=(2,))
        x, a = blk(layers_p[f"layer_{i}"], x, cfg)
        aux = aux + a
    return x, aux


def _encoder_apply(params, cfg, embeds):
    enc = params["encoder"]

    def body(carry, layer_p):
        h, = carry
        h, _ = tfm.block_apply(layer_p, h, cfg, "enc_attn")
        return (h,), None

    (x,), _ = jax.lax.scan(jax.checkpoint(body), (embeds,), enc["layers"])
    return rms_norm(x, enc["final_norm"], cfg.norm_eps)


def _embed_inputs(params, cfg, batch):
    dt = compute_dtype(cfg)
    x = take_embedding(params["embed"], batch["tokens"], dt)
    if cfg.family == "vlm":
        x = jnp.concatenate([batch["vision_embeds"].astype(dt), x], axis=1)
    return constrain_logical(x, ("batch", "seq", "act_embed"))


def _unembed(params, cfg, x):
    if cfg.tie_embeddings:
        w = params["embed"].astype(x.dtype)
        logits = jnp.einsum("bsd,vd->bsv", x, w)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["unembed"].astype(x.dtype))
    return constrain_logical(logits.astype(jnp.float32),
                             ("batch", "seq", "vocab"))


def forward(params, cfg, batch) -> tuple[jax.Array, jax.Array]:
    """Full-sequence forward. Returns (logits (B,S,V) float32, aux loss)."""
    kinds = tfm.layer_kinds(cfg)
    x = _embed_inputs(params, cfg, batch)
    memory = None
    if cfg.is_encdec:
        memory = _encoder_apply(params, cfg,
                                batch["audio_embeds"].astype(x.dtype))
    x, aux = _stack_apply(params, x, cfg, kinds, memory=memory)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _unembed(params, cfg, x), aux


def loss_fn(params, cfg, batch) -> jax.Array:
    """Next-token cross entropy (+ MoE aux). VLM skips the vision prefix."""
    logits, aux = forward(params, cfg, batch)
    F = cfg.frontend_tokens if cfg.family == "vlm" else 0
    tokens = batch["tokens"]
    preds = logits[:, F:F + tokens.shape[1] - 1]         # predicts tokens[1:]
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(preds, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    if mask is not None:
        m = mask[:, 1:].astype(jnp.float32)
        ce = -jnp.sum(ll * m) / jnp.maximum(jnp.sum(m), 1.0)
    else:
        ce = -jnp.mean(ll)
    return ce + aux


# -------------------------------------------------------------------- cache
def _layer_cache_shapes(cfg, kind: str, batch: int, max_len: int, dtype):
    K, Dh = cfg.num_kv_heads, cfg.head_dim
    if kind == "ssm":
        return ssm_cache_shapes(cfg, batch, dtype)
    if kind == "rglru":
        return rglru_cache_shapes(cfg, batch, dtype)
    if cfg.kv_lora_rank:        # one latent row a token, no head axis
        width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        return {"latent": ((batch, max_len, width), dtype)}
    slots = max_len
    if kind == "local_attn" or cfg.attention == "swa":
        slots = min(cfg.window, max_len)
    c = {"k": ((batch, K, slots, Dh), dtype), "v": ((batch, K, slots, Dh), dtype)}
    if kind == "cross":
        F = cfg.frontend_tokens
        c["enc_k"] = ((batch, F, K, Dh), dtype)
        c["enc_v"] = ((batch, F, K, Dh), dtype)
    return c


def cache_shapes(cfg, batch: int, max_len: int, dtype=None) -> dict:
    """Nested {name: (shape, dtype)} decode-cache description."""
    dtype = compute_dtype(cfg) if dtype is None else dtype
    kinds = tfm.layer_kinds(cfg)
    if _uniform_scan(cfg):
        lead = cfg.first_dense_layers
        out = {}
        for group, kind, n in (("dense_layers", kinds[0], lead),
                               ("layers", kinds[-1], cfg.num_layers - lead)):
            if n:
                per = _layer_cache_shapes(cfg, kind, batch, max_len, dtype)
                out[group] = {k: ((n, *shape), dt)
                              for k, (shape, dt) in per.items()}
        return out
    return {"layers": {f"layer_{i}": _layer_cache_shapes(cfg, k, batch,
                                                         max_len, dtype)
                       for i, k in enumerate(kinds)}}


def _is_shape_leaf(x):
    return (isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple))


def init_cache(cfg, batch: int, max_len: int, dtype=None):
    return jax.tree_util.tree_map(
        lambda sd: jnp.zeros(sd[0], sd[1]),
        cache_shapes(cfg, batch, max_len, dtype), is_leaf=_is_shape_leaf)


def abstract_cache(cfg, batch: int, max_len: int, dtype=None):
    return jax.tree_util.tree_map(
        lambda sd: jax.ShapeDtypeStruct(sd[0], sd[1]),
        cache_shapes(cfg, batch, max_len, dtype), is_leaf=_is_shape_leaf)


# ------------------------------------------------------------------- decode
def decode_step(params, cfg, cache, tokens, pos):
    """One decode step. tokens: (B, 1) int32; pos: scalar int32 (absolute
    position of this token). Returns (logits (B, V) f32, new_cache)."""
    kinds = tfm.layer_kinds(cfg)
    dt = compute_dtype(cfg)
    x = take_embedding(params["embed"], tokens, dt)
    layers_c = cache["layers"]
    out = {}
    if _uniform_scan(cfg):
        # leading layers unrolled, each writing its rows into its stack
        for i in range(cfg.first_dense_layers):
            x, out["dense_layers"] = tfm.block_decode(
                _layer(params["dense_layers"], i), x,
                out.get("dense_layers", cache.get("dense_layers")), pos, cfg,
                kinds[i], layer=i)
        kind = kinds[-1]
        sliced, whole = moe_mod.scan_split(params["layers"])

        # the stacked cache rides in the carry, so each layer's row writes
        # land in the donated buffer in place (passed as scan xs/ys, every
        # layer's whole cache is sliced out, written back and copied)
        def body(carry, layer):
            h, c = carry
            layer_p, i = layer
            h, c = tfm.block_decode({**layer_p, **whole}, h, c, pos, cfg,
                                    kind, layer=i)
            return (h, c), None

        (x, out["layers"]), _ = jax.lax.scan(
            body, (x, layers_c),
            (sliced, jnp.arange(cfg.num_layers - cfg.first_dense_layers)))
    else:
        out["layers"] = {}
        for i, kind in enumerate(kinds):
            x, out["layers"][f"layer_{i}"] = tfm.block_decode(
                params["layers"][f"layer_{i}"], x, layers_c[f"layer_{i}"],
                pos, cfg, kind)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _unembed(params, cfg, x)[:, 0]
    return logits, out


# ------------------------------------------------------------------ prefill
def prefill(params, cfg, batch, max_len: int):
    """Process the prompt, build the decode cache.

    Returns (last_logits (B, V) f32, cache). For enc-dec, also encodes the
    audio memory into per-layer cross K/V cache entries.
    """
    kinds = tfm.layer_kinds(cfg)
    x = _embed_inputs(params, cfg, batch)
    memory = None
    if cfg.is_encdec:
        memory = _encoder_apply(params, cfg,
                                batch["audio_embeds"].astype(x.dtype))
    layers_p = params["layers"]
    out = {}
    if _uniform_scan(cfg):
        lead = []
        for i in range(cfg.first_dense_layers):
            x, c, _ = tfm.block_prefill(_layer(params["dense_layers"], i), x,
                                        cfg, kinds[i], max_len, memory=memory)
            lead.append(c)
        if lead:
            out["dense_layers"] = jax.tree_util.tree_map(
                lambda *cs: jnp.stack(cs), *lead)
        kind = kinds[-1]
        sliced, whole = moe_mod.scan_split(layers_p)

        def body(h, layer):
            layer_p, i = layer
            h, layer_cache, _ = tfm.block_prefill(
                {**layer_p, **whole}, h, cfg, kind, max_len, memory=memory,
                layer=i)
            return h, layer_cache

        n = cfg.num_layers - cfg.first_dense_layers
        x, out["layers"] = jax.lax.scan(body, x, (sliced, jnp.arange(n)))
    else:
        out["layers"] = {}
        for i, kind in enumerate(kinds):
            x, out["layers"][f"layer_{i}"], _ = tfm.block_prefill(
                layers_p[f"layer_{i}"], x, cfg, kind, max_len, memory=memory)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _unembed(params, cfg, x[:, -1:])[:, 0]
    return logits, out
