"""Block assembly: (norm → mixer → residual) [→ norm → FFN/MoE → residual].

One block type per layer "kind":
  attn        causal self-attention (full or sliding window per config) + FFN
              (the MoE layer in a mixture-of-experts model)
  attn_dense  causal self-attention + dense MLP: the leading dense layers of
              a mixture-of-experts model (``first_dense_layers``)
  local_attn  sliding-window attention (hybrid archs) + FFN
  rglru       RG-LRU recurrent mixer + FFN
  kda         Kimi Delta Attention (gated delta-rule linear attention) +
              FFN (the MoE layer in a mixture-of-experts model)
  kda_dense   Kimi Delta Attention + dense MLP (a leading dense layer)
  ssm         Mamba-2 SSD mixer (no FFN — the mamba block subsumes it)
  enc_attn    bidirectional self-attention (encoder) + FFN
  cross       causal self-attention + cross-attention + FFN (decoder of
              an encoder-decoder)

Self-attention is latent attention (MLA) where ``cfg.kv_lora_rank`` is set;
its cache is one ``latent`` leaf of rows shared by the heads. Where
``cfg.kda_layers`` lists the published KDA layers (Kimi Linear), every other
layer is latent attention; a KDA layer's cache is its conv tails and its
recurrent state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import kda as kda_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import ParamSpec, geglu, rms_norm, swiglu

__all__ = ["layer_kinds", "block_specs", "block_apply", "block_decode",
           "block_prefill", "mlp_apply"]


def layer_kinds(cfg, *, encoder: bool = False) -> list[str]:
    if encoder:
        return ["enc_attn"] * cfg.encoder_layers
    if cfg.kda_layers:
        lead = cfg.first_dense_layers
        return [("kda" if i + 1 in cfg.kda_layers else "attn")
                + ("_dense" if i < lead else "")
                for i in range(cfg.num_layers)]
    if cfg.family == "ssm":
        return ["ssm"] * cfg.num_layers
    if cfg.family == "hybrid":
        pat = list(cfg.block_pattern)
        return [pat[i % len(pat)] for i in range(cfg.num_layers)]
    if cfg.is_encdec:
        return ["cross"] * cfg.num_layers
    lead = cfg.first_dense_layers
    return ["attn_dense"] * lead + ["attn"] * (cfg.num_layers - lead)


def _moe(cfg, kind: str) -> bool:
    return cfg.num_experts > 0 and kind in ("attn", "local_attn", "kda")


def _kda(kind: str) -> bool:
    return kind in ("kda", "kda_dense")


# ------------------------------------------------------------------- specs
def mlp_specs(cfg) -> dict:
    D, F = cfg.d_model, cfg.d_ff
    if cfg.mlp_variant == "gelu":
        return {"wi": ParamSpec((D, F), ("embed", "ff")),
                "wo_mlp": ParamSpec((F, D), ("ff", "embed"))}
    return {"wi_gate": ParamSpec((D, F), ("embed", "ff")),
            "wi_up": ParamSpec((D, F), ("embed", "ff")),
            "wo_mlp": ParamSpec((F, D), ("ff", "embed"))}


def block_specs(cfg, kind: str) -> dict:
    D = cfg.d_model
    s: dict = {"pre_norm": ParamSpec((D,), ("embed",), init="ones")}
    if _kda(kind):
        s.update(kda_mod.kda_specs(cfg))
    elif kind in ("attn", "attn_dense") and cfg.kv_lora_rank:
        s.update(attn.mla_specs(cfg))
    elif kind in ("attn", "attn_dense", "local_attn", "enc_attn", "cross"):
        s.update(attn.attn_specs(cfg))
    elif kind == "rglru":
        s.update(rglru_mod.rglru_specs(cfg))
    elif kind == "ssm":
        s.update(ssm_mod.ssm_specs(cfg))
        return s                                     # mamba block: mixer only
    else:
        raise ValueError(kind)
    if kind == "cross":
        s["cross_norm"] = ParamSpec((D,), ("embed",), init="ones")
        s["cross"] = attn.attn_specs(cfg, cross=True)
    s["mlp_norm"] = ParamSpec((D,), ("embed",), init="ones")
    if _moe(cfg, kind):
        s.update(moe_mod.moe_specs(cfg))
    else:
        s.update(mlp_specs(cfg))
    return s


# ------------------------------------------------------------------- apply
def mlp_apply(p: dict, x: jax.Array, cfg) -> jax.Array:
    if cfg.mlp_variant == "gelu":
        h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["wi"].astype(x.dtype)))
    else:
        h = swiglu(jnp.einsum("bsd,df->bsf", x, p["wi_gate"].astype(x.dtype)),
                   jnp.einsum("bsd,df->bsf", x, p["wi_up"].astype(x.dtype)))
    return jnp.einsum("bsf,fd->bsd", h, p["wo_mlp"].astype(x.dtype))


def _ffn(p: dict, x: jax.Array, cfg, kind: str, *, serving: bool = False,
         layer: jax.Array | None = None):
    """The MLP or MoE sublayer with its residual. ``serving`` (prefill and
    decode) routes an MoE dropless; training keeps the capacity dispatch.
    With ``layer``, ``p``'s expert weights are stacked over layers."""
    h = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
    if _moe(cfg, kind) and serving:
        out, aux = moe_mod.moe_serve(p, h, cfg, layer), jnp.float32(0.0)
    elif _moe(cfg, kind):
        out, aux = moe_mod.moe_apply(p, h, cfg)
    else:
        out, aux = mlp_apply(p, h, cfg), jnp.float32(0.0)
    return x + out, aux


def _window_for(cfg, kind: str) -> int | None:
    if kind == "local_attn" or cfg.attention == "swa":
        return cfg.window
    return None


def block_apply(p: dict, x: jax.Array, cfg, kind: str, *,
                memory=None) -> tuple[jax.Array, jax.Array]:
    """Train/eval full-sequence block. Returns (x, aux_loss)."""
    h = rms_norm(x, p["pre_norm"], cfg.norm_eps)
    if kind == "ssm":
        return x + ssm_mod.ssm_apply(p, h, cfg), jnp.float32(0.0)
    if kind == "rglru":
        x = x + rglru_mod.rglru_apply(p, h, cfg)
    elif _kda(kind):
        x = x + kda_mod.kda_apply(p, h, cfg)[0]
    elif cfg.kv_lora_rank and kind in ("attn", "attn_dense"):
        x = x + attn.mla_apply(p, h, cfg)[0]
    else:
        causal = kind != "enc_attn"
        x = x + attn.attn_apply(p, h, cfg, causal=causal,
                                window=_window_for(cfg, kind))
        if kind == "cross":
            hc = rms_norm(x, p["cross_norm"], cfg.norm_eps)
            x = x + attn.cross_attn_apply(p["cross"], hc, memory, cfg)
    return _ffn(p, x, cfg, kind)


# ------------------------------------------------------------------ prefill
def block_prefill(p: dict, x: jax.Array, cfg, kind: str, max_len: int, *,
                  memory=None, layer: jax.Array | None = None):
    """Like block_apply but also returns this layer's decode cache, padded
    to ``max_len`` slots (window-bounded for SWA/local). With ``layer`` this
    layer's index, ``p``'s expert weights are stacked over layers."""
    h = rms_norm(x, p["pre_norm"], cfg.norm_eps)
    aux = jnp.float32(0.0)
    if kind == "ssm":
        out, cache = _ssm_prefill(p, h, cfg)
        return x + out, cache, aux
    if kind == "rglru":
        out, cache = _rglru_prefill(p, h, cfg)
        x = x + out
        x, aux = _ffn(p, x, cfg, kind, serving=True, layer=layer)
        return x, cache, aux
    if _kda(kind):
        out, cache = kda_mod.kda_apply(p, h, cfg)
        x, aux = _ffn(p, x + out, cfg, kind, serving=True, layer=layer)
        return x, cache, aux
    if cfg.kv_lora_rank:
        out, rows = attn.mla_apply(p, h, cfg)
        x, aux = _ffn(p, x + out, cfg, kind, serving=True, layer=layer)
        return x, {"latent": _to_slots(rows, max_len, axis=1)}, aux
    window = _window_for(cfg, kind)
    out, (k, v) = attn.attn_apply(p, h, cfg, causal=True, window=window,
                                  return_kv=True)
    x = x + out
    slots = max_len if window is None else min(window, max_len)
    # head-major (B, K, slots, Dh)
    cache = {"k": _to_slots(jnp.swapaxes(k, 1, 2), slots, axis=2),
             "v": _to_slots(jnp.swapaxes(v, 1, 2), slots, axis=2)}
    if kind == "cross":
        mkv = attn.cross_memory_kv(p["cross"], memory)
        hc = rms_norm(x, p["cross_norm"], cfg.norm_eps)
        x = x + attn.cross_attn_apply(p["cross"], hc, mkv, cfg)
        cache = {**cache, "enc_k": mkv[0], "enc_v": mkv[1]}
    x, aux = _ffn(p, x, cfg, kind, serving=True, layer=layer)
    return x, cache, aux


def _to_slots(x: jax.Array, slots: int, axis: int) -> jax.Array:
    """Lay prefill rows, positions 0..S-1 along ``axis``, into a ring/flat
    cache of ``slots`` positions there."""
    S = x.shape[axis]
    if S >= slots:   # keep the last `slots` positions; ring phase = S % slots
        last = jax.lax.slice_in_dim(x, S - slots, S, axis=axis)
        return jnp.roll(last, S % slots, axis=axis)
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, slots - S)
    return jnp.pad(x, pad)


def _ssm_prefill(p, h, cfg):
    from repro.kernels.ssd.ref import ssd_ref
    B, S, D = h.shape
    d_inner, H, P, N, conv_dim = ssm_mod.ssm_dims(cfg)
    proj = jnp.einsum("bsd,de->bse", h, p["in_proj"].astype(h.dtype))
    z, xs, Bm, Cm, dt = ssm_mod._split(proj, cfg)
    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)
    tail = conv_in[:, -(cfg.conv_width - 1):, :]
    if S < cfg.conv_width - 1:
        tail = jnp.pad(conv_in, ((0, 0), (cfg.conv_width - 1 - S, 0), (0, 0)))
    conv_out = jax.nn.silu(ssm_mod._causal_conv(
        conv_in, p["conv_w"].astype(h.dtype), p["conv_b"].astype(h.dtype)))
    xs2 = conv_out[..., :d_inner]
    Bm2 = conv_out[..., d_inner:d_inner + N]
    Cm2 = conv_out[..., d_inner + N:]
    dtf = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    xh = xs2.reshape(B, S, H, P)
    y, state = ssd_ref(xh, dtf, A, Bm2, Cm2, chunk=cfg.ssm_chunk,
                       return_state=True)
    y = y + p["D_skip"].astype(h.dtype)[None, None, :, None] * xh
    y = rms_norm(y.reshape(B, S, d_inner) * jax.nn.silu(z), p["gate_norm"],
                 cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(h.dtype))
    return out, {"conv": tail, "state": state}


def _rglru_prefill(p, h, cfg):
    u = jnp.einsum("bsd,dw->bsw", h, p["in_x"].astype(h.dtype))
    S = u.shape[1]
    tail = u[:, -(cfg.conv_width - 1):, :]
    if S < cfg.conv_width - 1:
        tail = jnp.pad(u, ((0, 0), (cfg.conv_width - 1 - S, 0), (0, 0)))
    uc = rglru_mod._causal_conv(u, p["conv_w"].astype(h.dtype),
                                p["conv_b"].astype(h.dtype))
    a, b = rglru_mod._gates(p, uc)
    hseq = rglru_mod.lru_scan(a, b)
    g = jax.nn.gelu(jnp.einsum("bsd,dw->bsw", h, p["in_gate"].astype(h.dtype)))
    out = jnp.einsum("bsw,wd->bsd", hseq * g, p["out_w"].astype(h.dtype))
    return out, {"conv": tail, "h": hseq[:, -1].astype(jnp.float32)}


# ------------------------------------------------------------------- decode
# the one-token steps of the mixers whose cache is a recurrent state
_RECURRENT_DECODE = {"ssm": ssm_mod.ssm_decode,
                     "rglru": rglru_mod.rglru_decode,
                     "kda": kda_mod.kda_decode,
                     "kda_dense": kda_mod.kda_decode}


def block_decode(p: dict, x: jax.Array, cache: dict, pos: jax.Array, cfg,
                 kind: str, layer: jax.Array | None = None):
    """One-token step. x: (B, 1, D). ``cache`` holds this layer's leaves or,
    with ``layer`` this layer's index, every layer's leaves stacked on a
    leading axis (and so do ``p``'s expert weights). Each leaf is written as little as its kind needs:
    attention k/v one row per batch row, a recurrent state whole (it is
    O(B x state)), cross-attention memory not at all. Returns (x, cache)."""
    def read(c):
        return c if layer is None else c[layer]

    def write(c, new):
        return new if layer is None else c.at[layer].set(new)

    h = rms_norm(x, p["pre_norm"], cfg.norm_eps)
    if kind in _RECURRENT_DECODE:
        out, new = _RECURRENT_DECODE[kind](
            p, h, {k: read(c) for k, c in cache.items()}, cfg)
        x = x + out
        if kind != "ssm":
            x, _ = _ffn(p, x, cfg, kind, serving=True, layer=layer)
        return x, {k: write(cache[k], new[k]) for k in cache}
    if cfg.kv_lora_rank:
        out, latent = attn.mla_decode(p, h, cache["latent"], pos, cfg,
                                      layer=layer)
        x, _ = _ffn(p, x + out, cfg, kind, serving=True, layer=layer)
        return x, {"latent": latent}
    window = _window_for(cfg, kind)
    out, ck, cv = attn.attn_decode(p, h, cache["k"], cache["v"], pos, cfg,
                                   window=window, layer=layer)
    x = x + out
    new_cache = {**cache, "k": ck, "v": cv}
    if kind == "cross":
        hc = rms_norm(x, p["cross_norm"], cfg.norm_eps)
        x = x + attn.cross_attn_apply(
            p["cross"], hc, (read(cache["enc_k"]), read(cache["enc_v"])), cfg)
    x, _ = _ffn(p, x, cfg, kind, serving=True, layer=layer)
    return x, new_cache
