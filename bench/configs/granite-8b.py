"""Weights, plain float32 reference and arithmetic for granite-8b serving.

The model is a Llama-style decoder (arXiv:2405.04324): per layer RMSNorm,
grouped-query attention with rotary positions (rotate-half layout, the
first half of each head's dimensions paired with the second), causal
softmax scaled by ``head_dim**-0.5``, then RMSNorm and a SwiGLU MLP, each
added to the residual; a final RMSNorm and an untied output head. The
reference is written in ``jax.numpy`` and imports nothing of the program;
it runs one sequence at a time, layer by layer, at "highest" precision.

Weights are random, made on the device from the seed in one jitted call,
in the layout the program's parameter tree uses: ``embed`` (V, D),
``final_norm`` (D,), ``unembed`` (D, V) and ``layers`` stacked over the
layer dimension: ``pre_norm``, ``wq`` (D, H, Dh), ``wk`` and ``wv``
(D, K, Dh), ``wo`` (H, Dh, D), ``mlp_norm``, ``wi_gate`` and ``wi_up``
(D, F), ``wo_mlp`` (F, D). Matrices are normal with standard deviation
``fan_in**-0.5``, the embedding and the head 0.02; norms are one.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def dims(c: dict) -> dict:
    return {"D": c["hidden_size"], "L": c["num_hidden_layers"],
            "H": c["num_attention_heads"], "K": c["num_key_value_heads"],
            "Dh": c["head_dim"], "F": c["intermediate_size"],
            "V": c["vocab_size"]}


def _layer_shapes(c: dict) -> dict:
    d = dims(c)
    D, H, K, Dh, F = d["D"], d["H"], d["K"], d["Dh"], d["F"]
    return {"pre_norm": ((D,), None), "wq": ((D, H, Dh), D),
            "wk": ((D, K, Dh), D), "wv": ((D, K, Dh), D),
            "wo": ((H, Dh, D), H * Dh), "mlp_norm": ((D,), None),
            "wi_gate": ((D, F), D), "wi_up": ((D, F), D),
            "wo_mlp": ((F, D), F)}


def make_weights(c: dict, seed: int, dtype) -> dict:
    """The served weights, in ``dtype``, made on the device from the seed."""
    d = dims(c)
    L, D, V = d["L"], d["D"], d["V"]
    names = sorted(_layer_shapes(c))

    def make(key):
        keys = iter(jax.random.split(key, 2 + len(names)))

        def normal(shape, std):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * std).astype(dtype)

        out = {"embed": normal((V, D), 0.02), "unembed": normal((D, V), 0.02),
               "final_norm": jnp.ones((D,), dtype), "layers": {}}
        for name in names:
            shape, fan_in = _layer_shapes(c)[name]
            out["layers"][name] = (jnp.ones((L, *shape), dtype)
                                   if fan_in is None else
                                   normal((L, *shape), fan_in ** -0.5))
        return out

    # the key is an argument, so one compiled program serves every seed
    return jax.jit(make)(jax.random.PRNGKey(seed))


# -------------------------------------------------------------- reference
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (S, heads, Dh); rotate-half rotary embedding at positions 0..S-1."""
    S, _, Dh = x.shape
    half = Dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, p, c, num):
    """One block; ``num.act`` marks each activation the program holds in its
    compute precision."""
    mm, act = num
    d = dims(c)
    H, K, Dh = d["H"], d["K"], d["Dh"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    S = x.shape[0]
    h = act(_rms(x, p["pre_norm"], eps))
    q = act(_rope(mm("sd,dhk->shk", h, p["wq"]), theta))
    k = act(_rope(mm("sd,dhk->shk", h, p["wk"]), theta))
    v = act(mm("sd,dhk->shk", h, p["wv"]))
    q = q.reshape(S, K, H // K, Dh)
    s = mm("sgrk,tgk->grst", q, k) * Dh ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = mm("grst,tgk->sgrk", jax.nn.softmax(s, axis=-1), v).reshape(S, H, Dh)
    x = act(x + act(mm("shk,hkd->sd", act(o), p["wo"])))
    h = act(_rms(x, p["mlp_norm"], eps))
    up = act(jax.nn.silu(mm("sd,df->sf", h, p["wi_gate"]))
             * mm("sd,df->sf", h, p["wi_up"]))
    return act(x + act(mm("sf,fd->sd", up, p["wo_mlp"])))


def logits(params, toks, c, num):
    """Logits (S, V) of one sequence at every position, float32."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    x = num.act(f32(params["embed"])[toks])
    for i in range(c["num_hidden_layers"]):
        layer = {k: f32(v[i]) for k, v in params["layers"].items()}
        x = _layer(x, layer, c, num)
    x = num.act(_rms(x, f32(params["final_norm"]), c["rms_norm_eps"]))
    return num.mm("sd,dv->sv", x, f32(params["unembed"]))


# ------------------------------------------------------------- arithmetic
def layer_params(c: dict) -> int:
    return sum(math.prod(s) for s, _ in _layer_shapes(c).values())


def token_flops(c: dict, context: int) -> float:
    """FLOPs of one token through the model with ``context`` positions in
    its attention (itself included): 2 per weight of every layer matrix and
    the output head, and 2 x 2 x H x Dh per attended position and layer
    (scores and the weighted sum)."""
    d = dims(c)
    mats = d["L"] * (layer_params(c) - 2 * d["D"]) + d["D"] * d["V"]
    return 2.0 * mats + 4.0 * d["L"] * d["H"] * d["Dh"] * context


def prefill_flops(c: dict, length: int) -> float:
    """A causal prefill: token i attends to i + 1 positions."""
    d = dims(c)
    return length * token_flops(c, 0) + \
        4.0 * d["L"] * d["H"] * d["Dh"] * length * (length + 1) / 2


def decode_cost(c: dict, live: list[int], weight_bytes: int = 2,
                cache_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) one decode call needs for active rows whose caches
    hold ``live`` positions each after this token is written: every weight
    read once (the embedding only for the rows' tokens) and each row's live
    keys and values read, the new ones written."""
    d = dims(c)
    flops = sum(token_flops(c, n) for n in live)
    weights = (d["L"] * layer_params(c) + d["D"] + d["D"] * d["V"]) \
        * weight_bytes + len(live) * d["D"] * weight_bytes
    kv_row = d["L"] * 2 * d["K"] * d["Dh"] * cache_bytes
    return flops, weights + kv_row * (sum(live) + len(live))


def tokens(c: dict, rng: np.random.Generator, length: int) -> list[int]:
    return rng.integers(0, c["vocab_size"], length).tolist()
