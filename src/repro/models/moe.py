"""Mixture-of-Experts layer: top-k routing, a dropless grouped path for
serving and a capacity dispatch for training.

Routing is softmax over the experts (Mixtral) or, with ``router ==
"sigmoid"``, DeepSeek-V3's: sigmoid scores in float32, the top k chosen on
the score plus a per-expert choice bias (``router_bias``,
``e_score_correction_bias``), gated by the unbiased scores of the chosen
experts. Either way the gates are normalised to sum to one and scaled by
``routed_scaling``. Shared experts (``num_shared_experts``) are one SwiGLU
of that many times ``moe_d_ff`` that every token passes through.

Serving (prefill and decode, :func:`moe_serve`) is dropless: each token's
k assignments are sorted by expert and run through grouped matrix products
(``jax.lax.ragged_dot``), so the work is that of the assignments and each
expert's weights are read once per call, whatever the batch. Inside a layer
scan the expert weights stay stacked over the layers and the layer's
experts are groups among all layers' (the others empty): a grouped product
copies an operand that is a slice, so slicing out a layer's experts would
copy them whole on every call.

Training (:func:`moe_apply`) keeps the capacity dispatch: expert weight
tensors carry the `experts` logical axis (→ mesh `model` axis), and the
dispatch/combine einsums lower to the all-to-all pattern under pjit.
Tokens above capacity are dropped (MaxText-style), so every shape stays
static for SPMD. Its router aux (load-balancing) loss follows
Switch/Mixtral: ``E · Σ_e f_e · p_e`` with f the dispatch fraction and p
the mean normalised router score per expert.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import ParamSpec, swiglu
from repro.parallel.ctx import constrain_logical

__all__ = ["moe_specs", "route", "moe_apply", "moe_serve", "scan_split"]

_EXPERT_WEIGHTS = ("we_gate", "we_up", "we_down")


def moe_specs(cfg) -> dict:
    D, E, F = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    s = {
        "router": ParamSpec((D, E), ("embed", "experts")),
        "we_gate": ParamSpec((E, D, F), ("experts", "embed", "ff"),
                             fan_in_axes=(1,)),
        "we_up": ParamSpec((E, D, F), ("experts", "embed", "ff"),
                           fan_in_axes=(1,)),
        "we_down": ParamSpec((E, F, D), ("experts", "ff", "embed"),
                             fan_in_axes=(1,)),
    }
    if cfg.router == "sigmoid":
        s["router_bias"] = ParamSpec((E,), ("experts",), init="zeros")
    if cfg.num_shared_experts:
        Fs = cfg.num_shared_experts * F
        s["shared_gate"] = ParamSpec((D, Fs), ("embed", "ff"))
        s["shared_up"] = ParamSpec((D, Fs), ("embed", "ff"))
        s["shared_down"] = ParamSpec((Fs, D), ("ff", "embed"))
    return s


def route(p: dict, x: jax.Array, cfg):
    """x: (..., D) → (gates (..., k) float32, chosen experts (..., k), and
    the normalised scores (..., E) float32 the aux loss reads)."""
    k = cfg.num_experts_per_tok
    logits = jnp.einsum("...d,de->...e", x, p["router"].astype(x.dtype),
                        preferred_element_type=jnp.float32)
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, sel = jax.lax.top_k(scores + p["router_bias"].astype(jnp.float32),
                               k)
        gates = jnp.take_along_axis(scores, sel, axis=-1)
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates, sel = jax.lax.top_k(probs, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True) * cfg.routed_scaling
    return gates, sel, probs


def _shared(p: dict, x: jax.Array, cfg):
    if not cfg.num_shared_experts:
        return 0.0
    with jax.named_scope("moe.shared"):
        h = swiglu(jnp.einsum("...d,df->...f", x, p["shared_gate"].astype(x.dtype)),
                   jnp.einsum("...d,df->...f", x, p["shared_up"].astype(x.dtype)))
        return jnp.einsum("...f,fd->...d", h, p["shared_down"].astype(x.dtype))


def scan_split(group: dict) -> tuple[dict, dict]:
    """(what a serving layer scan slices per layer, the expert weights it
    keeps whole) of a stacked layer group: the grouped products take the
    experts stacked, with the layer's index, since a sliced operand is
    copied. A group without experts is all sliced."""
    whole = {k: v for k, v in group.items() if k in _EXPERT_WEIGHTS}
    return {k: v for k, v in group.items() if k not in whole}, whole


def moe_serve(p: dict, x: jax.Array, cfg,
              layer: jax.Array | None = None) -> jax.Array:
    """Dropless MoE for prefill and decode. x: (B, S, D) → (B, S, D). With
    ``layer``, the expert weights in ``p`` are stacked over layers, (L, E,
    ...), and this layer's index picks its experts."""
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    xt = x.reshape(B * S, D)
    weights = [p[name] for name in _EXPERT_WEIGHTS]
    with jax.named_scope("moe.route"):
        gates, sel, _ = route(p, xt, cfg)                          # (T, k)
        flat = sel.reshape(-1)
        order = jnp.argsort(flat, stable=True)     # assignments by expert
        xs = jnp.take(xt, order // k, axis=0)                      # (T·k, D)
        sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
        if layer is not None:
            L = weights[0].shape[0]
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((L * E,), jnp.int32), sizes, (layer * E,))
            weights = [w.reshape(L * E, *w.shape[2:]) for w in weights]
    with jax.named_scope("moe.experts"):
        def grouped(a, w):
            return jax.lax.ragged_dot(a, w.astype(x.dtype), sizes)

        wg, wu, wd = weights
        h = swiglu(grouped(xs, wg), grouped(xs, wu))
        ys = grouped(h, wd)                                        # (T·k, D)
        ys = jnp.take(ys, jnp.argsort(order), axis=0).reshape(B * S, k, D)
        y = jnp.einsum("tkd,tk->td", ys.astype(jnp.float32), gates)
    y = y.astype(x.dtype).reshape(B, S, D)
    return y + _shared(p, x, cfg)


def moe_apply(p: dict, x: jax.Array, cfg) -> tuple[jax.Array, jax.Array]:
    """Training: x: (B, S, D) → (out (B, S, D), aux_loss scalar)."""
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    capacity = max(int(S * k / E * cfg.capacity_factor), 1)

    gate_vals, sel, probs = route(p, x, cfg)                      # (B,S,k)

    onehot = jax.nn.one_hot(sel, E, dtype=jnp.float32)            # (B,S,k,E)
    assign = jnp.einsum("bske->bse", onehot)                      # 0/1
    # position of each token within its expert's buffer (per batch row)
    pos_in_expert = jnp.cumsum(assign, axis=1) - assign           # (B,S,E)
    keep = (assign > 0) & (pos_in_expert < capacity)
    slot = jax.nn.one_hot(pos_in_expert, capacity, dtype=jnp.float32)
    dispatch = jnp.where(keep[..., None], slot, 0.0)              # (B,S,E,C)
    gates_e = jnp.einsum("bske,bsk->bse", onehot, gate_vals)
    combine = dispatch * gates_e[..., None]                       # (B,S,E,C)

    xin = jnp.einsum("bsec,bsd->ebcd", dispatch.astype(x.dtype), x)
    xin = constrain_logical(xin, ("experts", "batch", "cap", "act_embed"))
    h = swiglu(jnp.einsum("ebcd,edf->ebcf", xin, p["we_gate"].astype(x.dtype)),
               jnp.einsum("ebcd,edf->ebcf", xin, p["we_up"].astype(x.dtype)))
    hout = jnp.einsum("ebcf,efd->ebcd", h, p["we_down"].astype(x.dtype))
    hout = constrain_logical(hout, ("experts", "batch", "cap", "act_embed"))
    out = jnp.einsum("bsec,ebcd->bsd", combine.astype(x.dtype), hout)
    out = constrain_logical(out + _shared(p, x, cfg),
                            ("batch", "seq", "act_embed"))

    # load-balancing aux loss
    frac_dispatch = jnp.mean(assign, axis=(0, 1))                 # (E,)
    frac_prob = jnp.mean(probs, axis=(0, 1))                      # (E,)
    aux = E * jnp.sum(frac_dispatch * frac_prob) * cfg.router_aux_coef
    return out, aux.astype(jnp.float32)
