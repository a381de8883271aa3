"""Weights, plain float32 reference and arithmetic for Moonlight-16B-A3B
serving.

The model is a DeepSeek-V3 decoder (huggingface.co/moonshotai/Moonlight-16B-A3B,
``model_type`` deepseek_v3). Each layer: RMSNorm, then latent attention
(MLA) added to the residual, then RMSNorm and an MLP added to it. The first
``first_k_dense_replace`` layers have a dense SwiGLU MLP; every later layer
a mixture of experts. A final RMSNorm and an untied output head.

Latent attention, in its published (non-absorbed) form: ``q = x·Wq`` per
head, split into a part without position (``qk_nope_head_dim``) and a rope
part (``qk_rope_head_dim``); ``x·Wkv_a`` gives the latent (``kv_lora_rank``,
RMS-normed) and one rope key part shared by every head. Rope rotates the
pairs (2i, 2i+1) at frequency ``rope_theta**(-2i/R)``. Per head, keys are
the latent times ``W_kb`` beside the shared rope key, values the latent
times ``W_vb``; causal softmax scaled by (nope + rope)**-0.5; the heads'
outputs through ``wo``. No rope scaling.

Mixture of experts: sigmoid scores of ``x·Wr`` in float32; the top
``num_experts_per_tok`` chosen on the score plus ``e_score_correction_bias``;
gates are the chosen experts' unbiased scores normalised to sum to one, times
``routed_scaling_factor``. Every expert is computed on every token and
weighted by its gate, which is zero where the expert was not chosen; the
shared experts, one SwiGLU ``n_shared_experts`` times as wide, are added for
every token.

The reference is written in ``jax.numpy`` and imports nothing of the
program; it runs one sequence at a time, layer by layer, at "highest"
precision. Weights are random, made on the device from the seed in one
jitted call, in the program's parameter layout: ``embed`` (V, D),
``final_norm`` (D,), ``unembed`` (D, V); ``dense_layers`` stacked over the
leading dense layers and ``layers`` over the expert layers, each with
``pre_norm``, ``wq`` (D, H, N+R), ``wkv_a`` (D, C+R), ``kv_norm`` (C,),
``wk_b`` (C, H, N), ``wv_b`` (C, H, Vd), ``wo`` (H, Vd, D), ``mlp_norm``;
the dense group ``wi_gate``, ``wi_up`` (D, F), ``wo_mlp`` (F, D); the expert
group ``router`` (D, E), ``router_bias`` (E,), ``we_gate``, ``we_up``
(E, D, Fe), ``we_down`` (E, Fe, D), ``shared_gate``, ``shared_up``
(D, Fs), ``shared_down`` (Fs, D). Matrices are normal with standard
deviation ``fan_in**-0.5``, the embedding and the head 0.02, the choice
bias 0.1 (non-zero, so a program that gates by the biased score, or chooses
by the unbiased one, serves other tokens); norms are one.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

BIAS_STD = 0.1


def dims(c: dict) -> dict:
    return {"D": c["hidden_size"], "L": c["num_hidden_layers"],
            "H": c["num_attention_heads"], "N": c["qk_nope_head_dim"],
            "R": c["qk_rope_head_dim"], "Vd": c["v_head_dim"],
            "C": c["kv_lora_rank"], "F": c["intermediate_size"],
            "Fe": c["moe_intermediate_size"], "E": c["n_routed_experts"],
            "k": c["num_experts_per_tok"],
            "Fs": c["n_shared_experts"] * c["moe_intermediate_size"],
            "lead": c["first_k_dense_replace"], "V": c["vocab_size"]}


def _attn_shapes(c: dict) -> dict:
    d = dims(c)
    D, H, N, R, Vd, C = d["D"], d["H"], d["N"], d["R"], d["Vd"], d["C"]
    return {"pre_norm": ((D,), None), "wq": ((D, H, N + R), D),
            "wkv_a": ((D, C + R), D), "kv_norm": ((C,), None),
            "wk_b": ((C, H, N), C), "wv_b": ((C, H, Vd), C),
            "wo": ((H, Vd, D), H * Vd), "mlp_norm": ((D,), None)}


def _dense_shapes(c: dict) -> dict:
    D, F = dims(c)["D"], dims(c)["F"]
    return {**_attn_shapes(c), "wi_gate": ((D, F), D), "wi_up": ((D, F), D),
            "wo_mlp": ((F, D), F)}


def _moe_shapes(c: dict) -> dict:
    d = dims(c)
    D, E, Fe, Fs = d["D"], d["E"], d["Fe"], d["Fs"]
    return {**_attn_shapes(c), "router": ((D, E), D),
            "router_bias": ((E,), "bias"),
            "we_gate": ((E, D, Fe), D), "we_up": ((E, D, Fe), D),
            "we_down": ((E, Fe, D), Fe), "shared_gate": ((D, Fs), D),
            "shared_up": ((D, Fs), D), "shared_down": ((Fs, D), Fs)}


def _groups(c: dict) -> dict:
    d = dims(c)
    return {"dense_layers": (_dense_shapes(c), d["lead"]),
            "layers": (_moe_shapes(c), d["L"] - d["lead"])}


def make_weights(c: dict, seed: int, dtype) -> dict:
    """The served weights, in ``dtype``, made on the device from the seed."""
    d = dims(c)
    D, V = d["D"], d["V"]
    groups = _groups(c)
    count = sum(len(shapes) for shapes, _ in groups.values())

    def make(key):
        keys = iter(jax.random.split(key, 2 + count))

        def normal(shape, std):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * std).astype(dtype)

        out = {"embed": normal((V, D), 0.02), "unembed": normal((D, V), 0.02),
               "final_norm": jnp.ones((D,), dtype)}
        for group, (shapes, n) in groups.items():
            out[group] = {}
            for name in sorted(shapes):
                shape, fan_in = shapes[name]
                out[group][name] = (
                    jnp.ones((n, *shape), dtype) if fan_in is None else
                    normal((n, *shape), BIAS_STD) if fan_in == "bias" else
                    normal((n, *shape), fan_in ** -0.5))
        return out

    # the key is an argument, so one compiled program serves every seed
    return jax.jit(make)(jax.random.PRNGKey(seed))


# -------------------------------------------------------------- reference
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (S, heads, R); the pairs (2i, 2i+1) rotated at positions 0..S-1."""
    S, _, R = x.shape
    freqs = theta ** (-jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                     -1).reshape(x.shape)


def _attention(h, p, c, num):
    """Latent attention of one sequence h (S, D), expanded per head."""
    mm, act = num
    d = dims(c)
    H, N, R, C = d["H"], d["N"], d["R"], d["C"]
    theta = c["rope_theta"]
    S = h.shape[0]
    q = act(mm("sd,dhk->shk", h, p["wq"]))
    kv = act(mm("sd,dc->sc", h, p["wkv_a"]))
    lat = act(_rms(kv[:, :C], p["kv_norm"], c["rms_norm_eps"]))
    k_pe = act(_rope(kv[:, None, C:], theta))
    q = jnp.concatenate([q[..., :N], act(_rope(q[..., N:], theta))], -1)
    k = jnp.concatenate([act(mm("sc,chn->shn", lat, p["wk_b"])),
                         jnp.broadcast_to(k_pe, (S, H, R))], -1)
    v = act(mm("sc,chv->shv", lat, p["wv_b"]))
    causal = jnp.tril(jnp.ones((S, S), bool))

    def head(qkv):
        qh, kh, vh = qkv
        s = mm("sk,tk->st", qh, kh) * (N + R) ** -0.5
        s = jnp.where(causal, s, -jnp.inf)
        return mm("st,tv->sv", jax.nn.softmax(s, axis=-1), vh)

    # one head at a time: a 5k-token sequence's scores fit beside the weights
    o = jax.lax.map(head, (jnp.swapaxes(q, 0, 1), jnp.swapaxes(k, 0, 1),
                           jnp.swapaxes(v, 0, 1)))             # (H, S, Vd)
    return act(mm("hsv,hvd->sd", act(o), p["wo"]))


def _swiglu(h, wg, wu, wd, num):
    mm, act = num
    up = act(jax.nn.silu(mm("sd,df->sf", h, wg)) * mm("sd,df->sf", h, wu))
    return act(mm("sf,fd->sd", up, wd))


def gates(h, p, c, num):
    """(S, E) float32 gates of the tokens h (S, D): zero where an expert
    was not chosen."""
    d = dims(c)
    scores = jax.nn.sigmoid(num.mm("sd,de->se", h, p["router"]))
    _, sel = jax.lax.top_k(scores + p["router_bias"], d["k"])
    chosen = jnp.sum(jax.nn.one_hot(sel, d["E"]), axis=1)
    g = scores * chosen
    return g / jnp.sum(g, -1, keepdims=True) * c["routed_scaling_factor"]


def _experts(h, p, c, num, experts=None):
    """Every expert on every token, weighted by its gate. ``experts`` maps
    each expert weight to (the weights stacked over layers, this layer's
    index), where they are not in ``p``."""
    g = gates(h, p, c, num)
    names = ("we_gate", "we_up", "we_down")
    stacked = experts or {k: (p[k][None], 0) for k in names}

    def one(acc, e):
        # indexed in the loop from the stacked weights: a layer's experts
        # sliced out first, or scanned over, would be copied whole
        wg, wu, wd = (jax.lax.dynamic_slice(
            w, (i, e, 0, 0), (1, 1, *w.shape[2:]))[0, 0]
            for w, i in (stacked[k] for k in names))
        ge = jax.lax.dynamic_index_in_dim(g, e, axis=1)
        return acc + ge * _swiglu(h, wg, wu, wd, num), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(g.shape[1]))
    shared = _swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"],
                     num)
    return num.act(num.act(y) + shared)


def _layer(x, p, c, num, experts=None):
    """One block, an expert layer where ``experts`` is given (as
    :func:`_experts` takes it); ``num.act`` marks each activation the
    program holds in its compute precision."""
    act, eps = num.act, c["rms_norm_eps"]
    x = act(x + _attention(act(_rms(x, p["pre_norm"], eps)), p, c, num))
    h = act(_rms(x, p["mlp_norm"], eps))
    if experts:
        return act(x + _experts(h, p, c, num, experts))
    return act(x + _swiglu(h, p["wi_gate"], p["wi_up"], p["wo_mlp"], num))


def logits(params, toks, c, num):
    """Logits (S, V) of one sequence at every position, float32. Weights
    stay in their stored precision until a product reads them (``num.mm``
    takes them to float32), so one expert's at a time is widened."""
    x = num.act(params["embed"][toks].astype(jnp.float32))
    for group, (_, n) in _groups(c).items():
        for i in range(n):
            experts = {k: (v, i) for k, v in params[group].items()
                       if k.startswith("we_")}
            layer = {k: v[i] for k, v in params[group].items()
                     if k not in experts}
            x = _layer(x, layer, c, num, experts)
    x = num.act(_rms(x, params["final_norm"].astype(jnp.float32),
                     c["rms_norm_eps"]))
    return num.mm("sd,dv->sv", x, params["unembed"])


# ------------------------------------------------------------- arithmetic
def _params(shapes: dict) -> int:
    return sum(math.prod(s) for s, _ in shapes.values())


def _counts(c: dict) -> dict:
    """Parameters: per-layer matrices a token multiplies (without norms and
    choice bias), the rest of each layer, and one expert."""
    d = dims(c)
    expert = 3 * d["D"] * d["Fe"]
    moe = _params(_moe_shapes(c))
    dense = _params(_dense_shapes(c))
    vectors = 2 * d["D"] + d["C"]                 # pre_norm, mlp_norm, kv_norm
    return {"expert": expert,
            "dense_mats": dense - vectors,
            "moe_mats": moe - vectors - d["E"] - d["E"] * expert,
            "dense_all": dense, "moe_shared": moe - d["E"] * expert}


def _attend(c: dict, absorbed: bool) -> float:
    """FLOPs per attended position and layer: the scores and the weighted
    sum, per head, over the expanded widths or (absorbed) the latent row."""
    d = dims(c)
    if absorbed:
        return 2.0 * d["H"] * ((d["C"] + d["R"]) + d["C"])
    return 2.0 * d["H"] * ((d["N"] + d["R"]) + d["Vd"])


def token_flops(c: dict, context: int, absorbed: bool = False) -> float:
    """FLOPs of one token with ``context`` positions in its attention
    (itself included): 2 per weight of every matrix it multiplies (its k
    experts, not all E) and of the head, and the attention per position."""
    d, n = dims(c), _counts(c)
    moe_layers = d["L"] - d["lead"]
    mats = (d["lead"] * n["dense_mats"]
            + moe_layers * (n["moe_mats"] + d["k"] * n["expert"])
            + d["D"] * d["V"])
    return 2.0 * mats + d["L"] * _attend(c, absorbed) * context


def prefill_flops(c: dict, length: int) -> float:
    """A causal prefill, expanded: token i attends to i + 1 positions."""
    d = dims(c)
    return length * token_flops(c, 0) + \
        d["L"] * _attend(c, False) * length * (length + 1) / 2


def _weight_bytes(c: dict, tokens: int, weight_bytes: int) -> float:
    """Every non-expert weight, the embedding rows of ``tokens`` tokens and
    the expected distinct experts they hit under uniform routing,
    E·(1 − (1 − k/E)^tokens) in each expert layer."""
    d, n = dims(c), _counts(c)
    hit = d["E"] * (1.0 - (1.0 - d["k"] / d["E"]) ** tokens)
    moe_layers = d["L"] - d["lead"]
    params = (d["lead"] * n["dense_all"]
              + moe_layers * (n["moe_shared"] + hit * n["expert"])
              + d["D"] + d["D"] * d["V"] + tokens * d["D"])
    return params * weight_bytes


def decode_cost(c: dict, live: list[int], weight_bytes: int = 2,
                cache_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) one absorbed decode call needs for active rows whose
    caches hold ``live`` positions each after this token is written: the
    weights (experts as hit by the rows), each row's live latent rows read,
    the new ones written."""
    d = dims(c)
    flops = sum(token_flops(c, n, absorbed=True) for n in live)
    row = d["L"] * (d["C"] + d["R"]) * cache_bytes
    return flops, _weight_bytes(c, len(live), weight_bytes) + \
        row * (sum(live) + len(live))


def prefill_cost(c: dict, length: int, weight_bytes: int = 2,
                 cache_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) a prefill of ``length`` tokens needs: the expanded
    causal attention, the weights (experts as hit by its tokens) and its
    latent rows written."""
    d = dims(c)
    row = d["L"] * (d["C"] + d["R"]) * cache_bytes
    return prefill_flops(c, length), \
        _weight_bytes(c, length, weight_bytes) + row * length


def tokens(c: dict, rng: np.random.Generator, length: int) -> list[int]:
    return rng.integers(0, c["vocab_size"], length).tolist()
