"""RG-LRU recurrent block (Griffin / RecurrentGemma).

Block: norm → two branches: (i) linear → causal conv → input/recurrence
gates → RG-LRU scan; (ii) linear → GeLU gate; merged by elementwise product
and an output projection. The recurrence

    a_t = exp(-c · softplus(Λ) · r_t),   r_t = σ(W_a u_t)
    h_t = a_t ⊙ h_{t-1} + sqrt(1 - a_t²) ⊙ (σ(W_x u_t) ⊙ u_t)

keeps |h| bounded; decode state is one (B, W) vector + a conv tail —
O(1) in context, so the hybrid runs the 500k decode cell.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.rglru import lru_scan, lru_decode_step
from repro.models.layers import ParamSpec

__all__ = ["rglru_specs", "rglru_apply", "rglru_decode", "rglru_cache_shapes"]

_C = 8.0  # Griffin's fixed decay sharpness


def rglru_specs(cfg) -> dict:
    D, W = cfg.d_model, cfg.lru_width
    return {
        "in_x": ParamSpec((D, W), ("embed", "ff")),
        "in_gate": ParamSpec((D, W), ("embed", "ff")),
        "conv_w": ParamSpec((cfg.conv_width, W), (None, "ff")),
        "conv_b": ParamSpec((W,), ("ff",), init="zeros"),
        "lam": ParamSpec((W,), ("ff",), init="ones"),
        "gate_a": ParamSpec((W, W), ("ff", None)),
        "gate_x": ParamSpec((W, W), ("ff", None)),
        "out_w": ParamSpec((W, D), ("ff", "embed")),
    }


def _gates(p, u):
    """u: (..., W) conv output → (a, b) recurrence coefficients."""
    r = jax.nn.sigmoid(jnp.einsum("...w,wv->...v", u, p["gate_a"].astype(u.dtype)))
    i = jax.nn.sigmoid(jnp.einsum("...w,wv->...v", u, p["gate_x"].astype(u.dtype)))
    log_a = (-_C * jax.nn.softplus(p["lam"].astype(jnp.float32))
             * r.astype(jnp.float32))
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * (i * u).astype(jnp.float32)
    return a.astype(u.dtype), b.astype(u.dtype)


def _causal_conv(u, w, b):
    W = w.shape[0]
    pad = jnp.pad(u, ((0, 0), (W - 1, 0), (0, 0)))
    return sum(pad[:, i:i + u.shape[1], :] * w[i] for i in range(W)) + b


def rglru_apply(p: dict, x: jax.Array, cfg) -> jax.Array:
    """Full-sequence recurrent mixer. x: (B, S, D) → (B, S, D)."""
    u = jnp.einsum("bsd,dw->bsw", x, p["in_x"].astype(x.dtype))
    u = _causal_conv(u, p["conv_w"].astype(x.dtype), p["conv_b"].astype(x.dtype))
    a, b = _gates(p, u)
    h = lru_scan(a, b)
    g = jax.nn.gelu(jnp.einsum("bsd,dw->bsw", x, p["in_gate"].astype(x.dtype)))
    return jnp.einsum("bsw,wd->bsd", h * g, p["out_w"].astype(x.dtype))


def rglru_cache_shapes(cfg, batch: int, dtype) -> dict:
    W = cfg.lru_width
    return {
        "conv": ((batch, cfg.conv_width - 1, W), dtype),
        "h": ((batch, W), jnp.float32),
    }


def rglru_decode(p: dict, x: jax.Array, cache: dict, cfg):
    """One-token step. x: (B, 1, D)."""
    u = jnp.einsum("bsd,dw->bsw", x, p["in_x"].astype(x.dtype))[:, 0]   # (B,W)
    hist = jnp.concatenate([cache["conv"], u[:, None, :]], axis=1)
    w = p["conv_w"].astype(x.dtype)
    u = jnp.einsum("bwc,wc->bc", hist, w) + p["conv_b"].astype(x.dtype)
    a, b = _gates(p, u)
    h = lru_decode_step(cache["h"], a.astype(jnp.float32), b.astype(jnp.float32))
    g = jax.nn.gelu(jnp.einsum("bsd,dw->bsw", x, p["in_gate"].astype(x.dtype)))[:, 0]
    out = jnp.einsum("bw,wd->bd", h.astype(x.dtype) * g,
                     p["out_w"].astype(x.dtype))[:, None, :]
    return out, {"conv": hist[:, 1:, :].astype(cache["conv"].dtype), "h": h}
