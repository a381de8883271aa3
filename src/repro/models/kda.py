"""Kimi Delta Attention (KDA): gated delta-rule linear attention, as Kimi
Linear publishes it (``KimiDeltaAttention``).

Per token t and head h, with a float32 state S of (d_k, d_v) per head:

    q, k, v  = SiLU(causal depthwise conv(x·W_{q,k,v}))      (no conv bias)
    q ← l2norm(q)·d_k^-1/2,   k ← l2norm(k)
    log α_t  = −exp(A_log_h) · softplus(x·W_fa·W_fb + dt_bias)   (per channel)
    β_t      = sigmoid(x·W_b)                                 (per head)
    S ← Diag(α_t)·S;   S ← S + β_t k_t (v_t − Sᵀk_t)ᵀ;   o_t = Sᵀq_t
    y        = RMSNorm(o_t)·w_norm ⊙ sigmoid(x·W_ga·W_gb),  then y·W_o

The state update is not a diagonal scan, so prefill runs the chunked (WY)
form: within a chunk of ``CHUNK`` tokens the updates are one unit
lower-triangular solve, and the state is carried from chunk to chunk. Every
decay factor there is ``exp(G_i − G_j)`` with ``G`` the chunk's cumulative
log decay and i ≥ j, so no exponent is positive. A prompt that is not a
whole number of chunks is padded with β = 0 and log α = 0, which leaves the
state as it is. Decode runs the one-token update on the cached state and the
conv tails (the last ``conv_width − 1`` inputs of the conv).

The products of the state are float32 at "highest" precision: the state is
carried over the whole context, and each token's update reads it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import ParamSpec, rms_norm

__all__ = ["CHUNK", "kda_specs", "kda_cache_shapes", "kda_apply",
           "kda_decode", "delta_chunked", "delta_step"]

CHUNK = 64
_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HI)


def kda_specs(cfg) -> dict:
    D, H, K, W = cfg.d_model, cfg.kda_heads, cfg.kda_head_dim, cfg.conv_width
    return {
        "wqkv": ParamSpec((D, 3, H, K), ("embed", None, "heads", "head"),
                          fan_in_axes=(0,)),
        "conv_w": ParamSpec((W, 3, H, K), (None, None, "heads", "head")),
        "wf_a": ParamSpec((D, K), ("embed", None)),
        "wf_b": ParamSpec((K, H, K), (None, "heads", "head")),
        "dt_bias": ParamSpec((H, K), ("heads", "head"), init="zeros"),
        "A_log": ParamSpec((H,), ("heads",), init="zeros"),
        "wb": ParamSpec((D, H), ("embed", "heads")),
        "wg_a": ParamSpec((D, K), ("embed", None)),
        "wg_b": ParamSpec((K, H, K), (None, "heads", "head")),
        "o_norm": ParamSpec((K,), (None,), init="ones"),
        "wo": ParamSpec((H, K, D), ("heads", "head", "embed"),
                        fan_in_axes=(0, 1)),
    }


def kda_cache_shapes(cfg, batch: int, dtype) -> dict:
    H, K = cfg.kda_heads, cfg.kda_head_dim
    return {"conv": ((batch, cfg.conv_width - 1, 3, H, K), dtype),
            "state": ((batch, H, K, K), _F32)}


# ------------------------------------------------------------------ pieces
def _project(p, x):
    """(the conv's input x·W_{q,k,v} (B, S, 3, H, K) in x's dtype, the log
    decay (B, S, H, K) and β (B, S, H), both float32)."""
    dt = x.dtype
    u = jnp.einsum("bsd,dchk->bschk", x, p["wqkv"].astype(dt))
    f = jnp.einsum("bsj,jhk->bshk",
                   jnp.einsum("bsd,dj->bsj", x, p["wf_a"].astype(dt)),
                   p["wf_b"].astype(dt))
    g = -jnp.exp(p["A_log"].astype(_F32))[:, None] * jax.nn.softplus(
        f.astype(_F32) + p["dt_bias"].astype(_F32))
    beta = jax.nn.sigmoid(jnp.einsum("bsd,dh->bsh", x,
                                     p["wb"].astype(dt)).astype(_F32))
    return u, g, beta


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _qkv(c):
    """q, k, v (..., H, K) float32 from the conv's SiLU output (..., 3, H,
    K): q and k l2-normed, q scaled by K^-1/2."""
    c = c.astype(_F32)
    K = c.shape[-1]
    return _l2norm(c[..., 0, :, :]) * K ** -0.5, _l2norm(c[..., 1, :, :]), \
        c[..., 2, :, :]


def _out(p, x, o, cfg):
    """The gated RMSNorm of the heads' outputs o (B, S, H, K) float32,
    through W_o."""
    dt = x.dtype
    gate = jnp.einsum("bsj,jhk->bshk",
                      jnp.einsum("bsd,dj->bsj", x, p["wg_a"].astype(dt)),
                      p["wg_b"].astype(dt))
    y = rms_norm(o, p["o_norm"], cfg.norm_eps) * jax.nn.sigmoid(
        gate.astype(_F32))
    return jnp.einsum("bshk,hkd->bsd", y.astype(dt), p["wo"].astype(dt))


# -------------------------------------------------------------- delta rule
def delta_chunked(q, k, v, g, beta, state, chunk: int = CHUNK):
    """The delta rule over a sequence, a chunk at a time. q, k, g (B, S, H,
    K), v (B, S, H, V), beta (B, S, H), all float32, g the log decay (≤ 0);
    state (B, H, K, V) float32, the state before the first token. Returns
    (o (B, S, H, V), the state after the last token).

    Within a chunk, with G the cumulative log decay and S0 the state carried
    in, the updates u_i = β_i (v_i − S_iᵀk_i) solve the unit lower
    triangular system (I + diag(β)·A) U = diag(β)(V − (K ⊙ e^G) S0), with
    A_ij = Σ_d k_i k_j e^{G_i − G_j} for j < i; then o = (Q ⊙ e^G) S0 + P U,
    P_ij = Σ_d q_i k_j e^{G_i − G_j} for j ≤ i, and the state carried out
    is e^{G_C} ⊙ S0 + (K ⊙ e^{G_C − G})ᵀ U."""
    B, S, H, K = q.shape
    n = -(-S // chunk)

    def split(x):               # (B, S, H, ...) → (n, B, H, chunk, ...)
        x = jnp.pad(x, [(0, 0), (0, n * chunk - S)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape(B, n, chunk, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)

    G = jnp.cumsum(split(g), axis=3)
    below = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None]

    def step(S0, xs):
        qc, kc, vc, Gc, bc = xs
        # exp(G_i − G_j) where j ≤ i, 0 above the diagonal: no exponent > 0
        decay = jnp.exp(jnp.where(
            below, Gc[..., :, None, :] - Gc[..., None, :, :], -jnp.inf))
        kk = jnp.sum(kc[..., :, None, :] * decay * kc[..., None, :, :], -1)
        qk = jnp.sum(qc[..., :, None, :] * decay * kc[..., None, :, :], -1)
        eG = jnp.exp(Gc)
        rhs = bc[..., None] * (vc - _mm("bhck,bhkv->bhcv", kc * eG, S0))
        # only the strictly lower part of β·A is read: the diagonal is one
        U = jax.lax.linalg.triangular_solve(
            bc[..., :, None] * kk, rhs, left_side=True, lower=True,
            unit_diagonal=True)
        o = _mm("bhck,bhkv->bhcv", qc * eG, S0) + _mm("bhij,bhjv->bhiv", qk, U)
        last = Gc[..., -1:, :]
        S1 = jnp.exp(last[..., 0, :])[..., None] * S0 + _mm(
            "bhck,bhcv->bhkv", kc * jnp.exp(last - Gc), U)
        return S1, o

    state, o = jax.lax.scan(step, state, (split(q), split(k), split(v), G,
                                          split(beta)))
    o = jnp.moveaxis(jnp.moveaxis(o, 3, 2), 0, 1)      # (B, n, chunk, H, V)
    return o.reshape(B, n * chunk, H, -1)[:, :S], state


def delta_step(q, k, v, g, beta, state):
    """One token of the delta rule: q, k, g (B, H, K), v (B, H, V), beta
    (B, H), state (B, H, K, V), float32. Returns (o (B, H, V), state).
    o = S'ᵀq + (k·q) u with S' the decayed state and u the update, so the
    state is read once for both of its products."""
    S = state * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - _mm("bhk,bhkv->bhv", k, S))
    o = _mm("bhk,bhkv->bhv", q, S) + jnp.sum(q * k, -1, keepdims=True) * u
    return o, S + k[..., None] * u[..., None, :]


# ------------------------------------------------------------------- layer
def kda_apply(p: dict, x: jax.Array, cfg):
    """Full-sequence KDA from an empty state (train / prefill). x: (B, S,
    D). Returns (out (B, S, D), the decode cache: the conv tails and the
    state after the last token)."""
    B, S, _ = x.shape
    W = cfg.conv_width
    with jax.named_scope("kda.project"):
        u, g, beta = _project(p, x)
        pad = jnp.pad(u, ((0, 0), (W - 1, 0), (0, 0), (0, 0), (0, 0)))
        w = p["conv_w"].astype(x.dtype)
        c = jax.nn.silu(sum(pad[:, i:i + S] * w[i] for i in range(W)))
        q, k, v = _qkv(c)
    with jax.named_scope("kda.chunk"):
        H, K = k.shape[-2:]
        o, state = delta_chunked(q, k, v, g, beta,
                                 jnp.zeros((B, H, K, v.shape[-1]), _F32))
    with jax.named_scope("kda.out"):
        out = _out(p, x, o, cfg)
    return out, {"conv": pad[:, S:], "state": state}


def kda_decode(p: dict, x: jax.Array, cache: dict, cfg):
    """One-token step. x: (B, 1, D); cache: {conv (B, W−1, 3, H, K), state
    (B, H, K, K)}. Returns (out (B, 1, D), the new cache)."""
    with jax.named_scope("kda.project"):
        u, g, beta = _project(p, x)
        hist = jnp.concatenate([cache["conv"], u.astype(cache["conv"].dtype)],
                               axis=1)
        c = jax.nn.silu(jnp.einsum("bwchk,wchk->bchk", hist,
                                   p["conv_w"].astype(hist.dtype)))
        q, k, v = _qkv(c)
    with jax.named_scope("kda.recur"):
        o, state = delta_step(q, k, v, g[:, 0], beta[:, 0], cache["state"])
    with jax.named_scope("kda.out"):
        out = _out(p, x, o[:, None], cfg)
    return out, {"conv": hist[:, 1:], "state": state}
