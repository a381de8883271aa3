"""Mamba-2 (SSD) block: in-proj → causal depthwise conv → SSD scan → gated
norm → out-proj, plus the single-token recurrent decode path whose state
(conv tail + (H, P, N) SSM state) replaces the KV cache entirely — decode
memory is O(1) in context length, which is why mamba runs the 500k cell.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ssd import ssd, ssd_decode_step
from repro.models.layers import ParamSpec, rms_norm

__all__ = ["ssm_dims", "ssm_specs", "ssm_apply", "ssm_decode", "ssm_cache_shapes"]


def ssm_dims(cfg):
    d_inner = cfg.ssm_expand * cfg.d_model
    H = cfg.ssm_heads
    P = cfg.ssm_head_dim
    N = cfg.ssm_state
    assert H * P == d_inner, (H, P, d_inner)
    conv_dim = d_inner + 2 * N
    return d_inner, H, P, N, conv_dim


def ssm_specs(cfg) -> dict:
    D = cfg.d_model
    d_inner, H, P, N, conv_dim = ssm_dims(cfg)
    proj_out = 2 * d_inner + 2 * N + H          # z, x, B, C, dt
    return {
        "in_proj": ParamSpec((D, proj_out), ("embed", "ff")),
        "conv_w": ParamSpec((cfg.conv_width, conv_dim), (None, "ff")),
        "conv_b": ParamSpec((conv_dim,), ("ff",), init="zeros"),
        "A_log": ParamSpec((H,), (None,), init="zeros"),     # A = -exp(A_log)
        "dt_bias": ParamSpec((H,), (None,), init="zeros"),
        "D_skip": ParamSpec((H,), (None,), init="ones"),
        "gate_norm": ParamSpec((d_inner,), ("ff",), init="ones"),
        "out_proj": ParamSpec((d_inner, D), ("ff", "embed")),
    }


def _split(proj, cfg):
    d_inner, H, P, N, _ = ssm_dims(cfg)
    z = proj[..., :d_inner]
    xs = proj[..., d_inner:2 * d_inner]
    Bm = proj[..., 2 * d_inner:2 * d_inner + N]
    Cm = proj[..., 2 * d_inner + N:2 * d_inner + 2 * N]
    dt = proj[..., 2 * d_inner + 2 * N:]
    return z, xs, Bm, Cm, dt


def _causal_conv(u: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv, width W: y[t] = Σ_i w[i]·u[t-W+1+i] + b."""
    W = w.shape[0]
    pad = jnp.pad(u, ((0, 0), (W - 1, 0), (0, 0)))
    y = sum(pad[:, i:i + u.shape[1], :] * w[i] for i in range(W))
    return y + b


def ssm_apply(p: dict, x: jax.Array, cfg) -> jax.Array:
    """Full-sequence SSD mixer. x: (B, S, D) → (B, S, D)."""
    B, S, D = x.shape
    d_inner, H, P, N, conv_dim = ssm_dims(cfg)
    proj = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(x.dtype))
    z, xs, Bm, Cm, dt = _split(proj, cfg)
    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)
    conv_out = jax.nn.silu(_causal_conv(conv_in, p["conv_w"].astype(x.dtype),
                                        p["conv_b"].astype(x.dtype)))
    xs = conv_out[..., :d_inner]
    Bm = conv_out[..., d_inner:d_inner + N]
    Cm = conv_out[..., d_inner + N:]
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))     # (B,S,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))                 # (H,)
    xh = xs.reshape(B, S, H, P)
    y = ssd(xh, dt, A, Bm, Cm, chunk=cfg.ssm_chunk)
    y = y + p["D_skip"].astype(x.dtype)[None, None, :, None] * xh
    y = y.reshape(B, S, d_inner)
    y = rms_norm(y * jax.nn.silu(z), p["gate_norm"], cfg.norm_eps)
    return jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(x.dtype))


# --------------------------------------------------------------------- decode
def ssm_cache_shapes(cfg, batch: int, dtype) -> dict:
    d_inner, H, P, N, conv_dim = ssm_dims(cfg)
    return {
        "conv": ((batch, cfg.conv_width - 1, conv_dim), dtype),
        "state": ((batch, H, P, N), jnp.float32),
    }


def ssm_decode(p: dict, x: jax.Array, cache: dict, cfg):
    """One-token step. x: (B, 1, D); cache: {conv (B,W-1,C), state (B,H,P,N)}."""
    B = x.shape[0]
    d_inner, H, P, N, conv_dim = ssm_dims(cfg)
    proj = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(x.dtype))[:, 0]
    z, xs, Bm, Cm, dt = _split(proj, cfg)
    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)             # (B, C)
    hist = jnp.concatenate([cache["conv"], conv_in[:, None, :]], axis=1)
    w = p["conv_w"].astype(x.dtype)
    conv_out = jax.nn.silu(jnp.einsum("bwc,wc->bc", hist, w)
                           + p["conv_b"].astype(x.dtype))
    new_conv = hist[:, 1:, :]
    xs = conv_out[:, :d_inner]
    Bm = conv_out[:, d_inner:d_inner + N]
    Cm = conv_out[:, d_inner + N:]
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))     # (B,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    xh = xs.reshape(B, H, P)
    y, new_state = ssd_decode_step(cache["state"], xh, dt, A, Bm, Cm)
    y = y + p["D_skip"].astype(x.dtype)[None, :, None] * xh
    y = y.reshape(B, d_inner)
    y = rms_norm(y * jax.nn.silu(z), p["gate_norm"], cfg.norm_eps)
    out = jnp.einsum("be,ed->bd", y, p["out_proj"].astype(x.dtype))[:, None, :]
    return out, {"conv": new_conv.astype(cache["conv"].dtype),
                 "state": new_state}
