"""Weights, plain float32 reference and arithmetic for Kimi-Linear-48B-A3B
serving.

The model is Kimi Linear (huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct,
``model_type`` kimi_linear). Each layer: RMSNorm, then a mixer added to the
residual, then RMSNorm and an MLP added to it. The mixer of the layers that
``linear_attn_config.kda_layers`` lists (1-based) is Kimi Delta Attention,
of those in ``full_attn_layers`` latent attention. The first
``first_k_dense_replace`` layers have a dense SwiGLU MLP; every later layer
a mixture of experts. A final RMSNorm and an untied output head.

Kimi Delta Attention, per token t and head h, the state S (d_k, d_v) in
float32 from zero: ``q, k, v = SiLU(causal depthwise conv(x·W_{q,k,v}))``
(no conv bias); ``q ← l2norm(q)·d_k^-1/2``, ``k ← l2norm(k)`` (eps 1e-6);
``log α = −exp(A_log_h)·softplus(x·W_fa·W_fb + dt_bias)`` per channel;
``β = sigmoid(x·W_b)``; then ``S ← Diag(α)·S``, ``S ← S + β k (v − Sᵀk)ᵀ``,
``o = Sᵀq``, computed here token by token; ``y = RMSNorm(o)·w_norm ⊙
sigmoid(x·W_ga·W_gb)`` over each head's d_v, then ``y·W_o``.

Latent attention without rotary embedding (``mla_use_nope``): ``q = x·Wq``
per head, ``x·Wkv_a`` gives the latent (``kv_lora_rank``, RMS-normed) and a
key part of ``qk_rope_head_dim`` shared by every head, left unrotated. Per
head, keys are the latent times ``W_kb`` beside the shared part, values the
latent times ``W_vb``; causal softmax scaled by (nope + rope)**-0.5,
computed a block of queries at a time; the heads' outputs through ``wo``.

Mixture of experts, held by share: sigmoid scores of ``x·Wr`` over all
``router_experts`` in float32; the top ``num_experts_per_token`` chosen on
the score plus ``e_score_correction_bias``; gates are the chosen experts'
unbiased scores normalised to sum to one, times ``routed_scaling_factor``.
This chip holds experts 0 to ``num_experts`` − 1 of each layer: each of
them is computed on every token and weighted by its gate, zero where it was
not chosen; the other experts' part lies on the other chips and is not
added. The shared expert, one SwiGLU ``num_shared_experts`` times as wide,
is added for every token.

The reference is written in ``jax.numpy`` and imports nothing of the
program; it runs one sequence at a time, layer by layer, at "highest"
precision. Weights are random, made on the device from the seed in one
jitted call, in the program's parameter layout: ``embed`` (V, D),
``final_norm`` (D,), ``unembed`` (D, V), and ``layers`` with one dict
``layer_i`` per layer, each with ``pre_norm`` and ``mlp_norm`` (D,). A KDA
layer: ``wqkv`` (D, 3, H, K), ``conv_w`` (W, 3, H, K), ``wf_a``, ``wg_a``
(D, K), ``wf_b``, ``wg_b`` (K, H, K), ``dt_bias`` (H, K), ``A_log`` (H,),
``wb`` (D, H), ``o_norm`` (K,), ``wo`` (H, K, D). A latent-attention layer:
``wq`` (D, Ha, N+R), ``wkv_a`` (D, C+R), ``kv_norm`` (C,), ``wk_b`` (C, Ha,
N), ``wv_b`` (C, Ha, Vd), ``wo`` (Ha, Vd, D). The dense MLP ``wi_gate``,
``wi_up`` (D, F), ``wo_mlp`` (F, D); the experts ``router`` (D, E),
``router_bias`` (E,), ``we_gate``, ``we_up`` (Eh, D, Fe), ``we_down`` (Eh,
Fe, D), ``shared_gate``, ``shared_up`` (D, Fs), ``shared_down`` (Fs, D).
Matrices are normal with standard deviation ``fan_in**-0.5``, the
embedding and the head 0.02, the choice bias 0.1 (non-zero, so a program
that gates by the biased score, or chooses by the unbiased one, serves
other tokens); ``A_log`` is log U(1, 16) and ``dt_bias`` the inverse
softplus of a log-uniform step in [0.001, 0.1], as the published layer
initialises them, both float32 as published; norms are one.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

BIAS_STD = 0.1
QUERY_BLOCK = 256            # queries a block of the expanded attention
HI = jax.lax.Precision.HIGHEST
# FLOPs of one token's update per element of a KDA state: the decay 1,
# Sᵀk 2, the rank-one update 2, Sᵀq 2
STATE_FLOPS = 7


def dims(c: dict) -> dict:
    la = c["linear_attn_config"]
    return {"D": c["hidden_size"], "L": c["num_hidden_layers"],
            "Ha": c["num_attention_heads"], "N": c["qk_nope_head_dim"],
            "R": c["qk_rope_head_dim"], "Vd": c["v_head_dim"],
            "C": c["kv_lora_rank"], "F": c["intermediate_size"],
            "Fe": c["moe_intermediate_size"], "E": c["router_experts"],
            "Eh": c["num_experts"], "k": c["num_experts_per_token"],
            "Fs": c["num_shared_experts"] * c["moe_intermediate_size"],
            "lead": c["first_k_dense_replace"], "V": c["vocab_size"],
            "H": la["num_heads"], "K": la["head_dim"],
            "W": la["short_conv_kernel_size"]}


def kinds(c: dict) -> list[str]:
    """Each layer's mixer, "kda" or "mla", from the published 1-based
    lists."""
    la = c["linear_attn_config"]
    out = []
    for i in range(1, c["num_hidden_layers"] + 1):
        assert (i in la["kda_layers"]) != (i in la["full_attn_layers"]), i
        out.append("kda" if i in la["kda_layers"] else "mla")
    return out


def _kda_shapes(c: dict) -> dict:
    d = dims(c)
    D, H, K, W = d["D"], d["H"], d["K"], d["W"]
    return {"wqkv": ((D, 3, H, K), D), "conv_w": ((W, 3, H, K), W),
            "wf_a": ((D, K), D), "wf_b": ((K, H, K), K),
            "dt_bias": ((H, K), "dt"), "A_log": ((H,), "A"),
            "wb": ((D, H), D), "wg_a": ((D, K), D), "wg_b": ((K, H, K), K),
            "o_norm": ((K,), None), "wo": ((H, K, D), H * K)}


def _mla_shapes(c: dict) -> dict:
    d = dims(c)
    D, H, N, R, Vd, C = d["D"], d["Ha"], d["N"], d["R"], d["Vd"], d["C"]
    return {"wq": ((D, H, N + R), D), "wkv_a": ((D, C + R), D),
            "kv_norm": ((C,), None), "wk_b": ((C, H, N), C),
            "wv_b": ((C, H, Vd), C), "wo": ((H, Vd, D), H * Vd)}


def _ffn_shapes(c: dict, dense: bool) -> dict:
    d = dims(c)
    D, F, E, Eh, Fe, Fs = d["D"], d["F"], d["E"], d["Eh"], d["Fe"], d["Fs"]
    if dense:
        return {"wi_gate": ((D, F), D), "wi_up": ((D, F), D),
                "wo_mlp": ((F, D), F)}
    return {"router": ((D, E), D), "router_bias": ((E,), "bias"),
            "we_gate": ((Eh, D, Fe), D), "we_up": ((Eh, D, Fe), D),
            "we_down": ((Eh, Fe, D), Fe), "shared_gate": ((D, Fs), D),
            "shared_up": ((D, Fs), D), "shared_down": ((Fs, D), Fs)}


def layer_shapes(c: dict, i: int) -> dict:
    D = dims(c)["D"]
    mixer = _kda_shapes(c) if kinds(c)[i] == "kda" else _mla_shapes(c)
    return {"pre_norm": ((D,), None), "mlp_norm": ((D,), None), **mixer,
            **_ffn_shapes(c, i < dims(c)["lead"])}


def make_weights(c: dict, seed: int, dtype) -> dict:
    """The served weights, in ``dtype``, made on the device from the seed."""
    d = dims(c)
    D, V, L = d["D"], d["V"], d["L"]
    shapes = [layer_shapes(c, i) for i in range(L)]

    def make(key):
        keys = iter(jax.random.split(key, 2 + sum(map(len, shapes))))

        def draw(shape, how):
            key = next(keys)
            if how is None:
                return jnp.ones(shape, dtype)
            # the decay's parameters stay float32, as published
            if how == "A":
                return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                                  1, 16))
            if how == "dt":
                dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                                math.log(1e-3), math.log(0.1)))
                return dt + jnp.log(-jnp.expm1(-dt))    # softplus(x) = dt
            std = BIAS_STD if how == "bias" else 0.02 if how == "embed" \
                else how ** -0.5
            return (jax.random.normal(key, shape, jnp.float32)
                    * std).astype(dtype)

        out = {"embed": draw((V, D), "embed"),
               "unembed": draw((D, V), "embed"),
               "final_norm": jnp.ones((D,), dtype), "layers": {}}
        for i, layer in enumerate(shapes):
            out["layers"][f"layer_{i}"] = {
                name: draw(*layer[name]) for name in sorted(layer)}
        return out

    # the key is an argument, so one compiled program serves every seed
    return jax.jit(make)(jax.random.PRNGKey(seed))


# -------------------------------------------------------------- reference
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta, state):
    """The gated delta rule token by token. q, k, g (S, H, K), v (S, H,
    Vd), beta (S, H), state (H, K, Vd), float32. Returns (o (S, H, Vd),
    the state after the last token)."""
    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[..., None]
        err = vt - jnp.einsum("hk,hkv->hv", kt, s, precision=HI)
        s = s + bt[:, None, None] * kt[..., None] * err[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", qt, s, precision=HI)

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


def _kda(h, p, c, num):
    """Kimi Delta Attention of one sequence h (S, D)."""
    mm, act = num
    d = dims(c)
    S, K, W = h.shape[0], d["K"], d["W"]
    u = act(mm("sd,dchk->schk", h, p["wqkv"]))
    pad = jnp.pad(u, ((W - 1, 0), (0, 0), (0, 0), (0, 0)))
    w = p["conv_w"].astype(jnp.float32)
    conv = act(jax.nn.silu(sum(pad[i:i + S] * w[i] for i in range(W))))
    q = _l2norm(conv[:, 0]) * K ** -0.5
    k = _l2norm(conv[:, 1])
    f = act(mm("sj,jhk->shk", act(mm("sd,dj->sj", h, p["wf_a"])), p["wf_b"]))
    g = -jnp.exp(p["A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
        f + p["dt_bias"].astype(jnp.float32))
    beta = jax.nn.sigmoid(act(mm("sd,dh->sh", h, p["wb"])))
    o, _ = delta_rule(q, k, conv[:, 2], g, beta,
                      jnp.zeros((d["H"], K, K), jnp.float32))
    gate = act(mm("sj,jhk->shk", act(mm("sd,dj->sj", h, p["wg_a"])),
                  p["wg_b"]))
    y = act(_rms(o, p["o_norm"].astype(jnp.float32), c["rms_norm_eps"])
            * jax.nn.sigmoid(gate))
    return act(mm("shk,hkd->sd", y, p["wo"]))


def _attention(h, p, c, num):
    """Latent attention of one sequence h (S, D), expanded per head, with
    nothing rotated; ``QUERY_BLOCK`` queries at a time (S a multiple of it,
    or shorter)."""
    mm, act = num
    d = dims(c)
    H, N, R, C = d["Ha"], d["N"], d["R"], d["C"]
    S = h.shape[0]
    q = act(mm("sd,dhk->shk", h, p["wq"]))
    kv = act(mm("sd,dc->sc", h, p["wkv_a"]))
    lat = act(_rms(kv[:, :C], p["kv_norm"].astype(jnp.float32),
                   c["rms_norm_eps"]))
    k = jnp.concatenate([act(mm("sc,chn->shn", lat, p["wk_b"])),
                         jnp.broadcast_to(kv[:, None, C:], (S, H, R))], -1)
    v = act(mm("sc,chv->shv", lat, p["wv_b"]))
    blk = min(QUERY_BLOCK, S)
    assert S % blk == 0, (S, blk)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk)
        s = mm("qhk,thk->hqt", qb, k) * (N + R) ** -0.5
        causal = (i * blk + jnp.arange(blk))[:, None] >= jnp.arange(S)
        s = jnp.where(causal, s, -jnp.inf)
        return mm("hqt,thv->qhv", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(S // blk)).reshape(S, H, d["Vd"])
    return act(mm("shv,hvd->sd", act(o), p["wo"]))


def _swiglu(h, wg, wu, wd, num):
    mm, act = num
    up = act(jax.nn.silu(mm("sd,df->sf", h, wg)) * mm("sd,df->sf", h, wu))
    return act(mm("sf,fd->sd", up, wd))


def gates(h, p, c, num):
    """(S, E) float32 gates of the tokens h (S, D) over all the router's
    experts: zero where an expert was not chosen."""
    d = dims(c)
    scores = jax.nn.sigmoid(num.mm("sd,de->se", h, p["router"]))
    _, sel = jax.lax.top_k(scores + p["router_bias"].astype(jnp.float32),
                           d["k"])
    chosen = jnp.sum(jax.nn.one_hot(sel, d["E"]), axis=1)
    g = scores * chosen
    return g / jnp.sum(g, -1, keepdims=True) * c["routed_scaling_factor"]


def _experts(h, p, c, num):
    """The held experts, every one on every token weighted by its gate,
    and the shared expert."""
    g = gates(h, p, c, num)

    def one(acc, e):
        wg, wu, wd = (jax.lax.dynamic_index_in_dim(p[n], e, keepdims=False)
                      for n in ("we_gate", "we_up", "we_down"))
        ge = jax.lax.dynamic_index_in_dim(g, e, axis=1)
        return acc + ge * _swiglu(h, wg, wu, wd, num), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        jnp.arange(p["we_gate"].shape[0]))
    shared = _swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"],
                     num)
    return num.act(num.act(y) + shared)


def layer(x, p, c, num, kind: str, dense: bool):
    """One block; ``num.act`` marks each activation the program holds in
    its compute precision."""
    act, eps = num.act, c["rms_norm_eps"]
    mixer = _kda if kind == "kda" else _attention
    h = act(_rms(x, p["pre_norm"].astype(jnp.float32), eps))
    x = act(x + mixer(h, p, c, num))
    h = act(_rms(x, p["mlp_norm"].astype(jnp.float32), eps))
    if dense:
        return act(x + _swiglu(h, p["wi_gate"], p["wi_up"], p["wo_mlp"], num))
    return act(x + _experts(h, p, c, num))


def logits(params, toks, c, num):
    """Logits (S, V) of one sequence at every position, float32. Weights
    stay in their stored precision until a product reads them."""
    lead = dims(c)["lead"]
    x = num.act(params["embed"][toks].astype(jnp.float32))
    for i, kind in enumerate(kinds(c)):
        x = layer(x, params["layers"][f"layer_{i}"], c, num, kind, i < lead)
    x = num.act(_rms(x, params["final_norm"].astype(jnp.float32),
                     c["rms_norm_eps"]))
    return num.mm("sd,dv->sv", x, params["unembed"])


# ------------------------------------------------------------- arithmetic
def _size(shapes: dict, *skip: str) -> int:
    return sum(math.prod(s) for name, (s, _) in shapes.items()
               if name not in skip)


def _expert(c: dict) -> int:
    d = dims(c)
    return 3 * d["D"] * d["Fe"]


def _mixer_mats(c: dict, kind: str) -> int:
    """Weights of a mixer that a token multiplies (its conv weights
    included, each once), without its norms, ``dt_bias`` and ``A_log``."""
    if kind == "kda":
        return _size(_kda_shapes(c), "o_norm", "dt_bias", "A_log")
    return _size(_mla_shapes(c), "kv_norm")


def _attend(c: dict, absorbed: bool) -> float:
    """FLOPs per attended position and latent-attention layer: the scores
    and the weighted sum, per head, over the expanded widths or (absorbed)
    the latent row."""
    d = dims(c)
    if absorbed:
        return 2.0 * d["Ha"] * ((d["C"] + d["R"]) + d["C"])
    return 2.0 * d["Ha"] * ((d["N"] + d["R"]) + d["Vd"])


def _state(c: dict) -> int:
    """Elements of one row's state in one KDA layer."""
    d = dims(c)
    return d["H"] * d["K"] * d["K"]


def token_flops(c: dict, context: int, absorbed: bool = False) -> float:
    """FLOPs of one token on this chip, with ``context`` positions in each
    latent-attention layer (itself included): 2 per weight of every matrix
    it multiplies (of the experts, the k·Eh/E of its k that this chip holds,
    as expected), of the head, the KDA state's update and the attention per
    position."""
    d = dims(c)
    ks = kinds(c)
    mats = sum(_mixer_mats(c, kind) for kind in ks) + d["D"] * d["V"]
    for i in range(d["L"]):
        if i < d["lead"]:
            mats += _size(_ffn_shapes(c, True))
        else:
            mats += _size(_ffn_shapes(c, False), "router_bias", "we_gate",
                          "we_up", "we_down") \
                + d["k"] * d["Eh"] / d["E"] * _expert(c)
    return 2.0 * mats + ks.count("kda") * STATE_FLOPS * _state(c) + \
        ks.count("mla") * _attend(c, absorbed) * context


def prefill_flops(c: dict, length: int) -> float:
    """A causal prefill, expanded: token i attends to i + 1 positions."""
    return length * token_flops(c, 0) + kinds(c).count("mla") * \
        _attend(c, False) * length * (length + 1) / 2


def _weight_bytes(c: dict, tokens: int, weight_bytes: int) -> float:
    """Every weight but the experts and the embedding, the embedding rows
    of ``tokens`` tokens and the expected distinct held experts they hit
    under uniform routing, Eh·(1 − (1 − k/E)^tokens) in each expert layer."""
    d = dims(c)
    hit = d["Eh"] * (1.0 - (1.0 - d["k"] / d["E"]) ** tokens)
    params = sum(_size(layer_shapes(c, i), "we_gate", "we_up", "we_down")
                 for i in range(d["L"]))
    params += (d["L"] - d["lead"]) * hit * _expert(c) + d["D"] + \
        d["D"] * d["V"] + tokens * d["D"]
    return params * weight_bytes


def _recurrent_bytes(c: dict, cache_bytes: int) -> int:
    """Bytes of one row's recurrent cache over the KDA layers: the float32
    states and the conv tails (the last W − 1 inputs of q, k and v)."""
    d = dims(c)
    tails = (d["W"] - 1) * 3 * d["H"] * d["K"] * cache_bytes
    return kinds(c).count("kda") * (_state(c) * 4 + tails)


def _latent_row(c: dict, cache_bytes: int) -> int:
    d = dims(c)
    return kinds(c).count("mla") * (d["C"] + d["R"]) * cache_bytes


def decode_cost(c: dict, live: list[int], weight_bytes: int = 2,
                cache_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) one absorbed decode call needs for active rows whose
    latent caches hold ``live`` positions each after this token is written:
    the weights (experts as hit by the rows), each row's KDA states and conv
    tails read and written, its live latent rows read and the new ones
    written."""
    flops = sum(token_flops(c, n, absorbed=True) for n in live)
    return flops, _weight_bytes(c, len(live), weight_bytes) + \
        2 * _recurrent_bytes(c, cache_bytes) * len(live) + \
        _latent_row(c, cache_bytes) * (sum(live) + len(live))


def prefill_cost(c: dict, length: int, weight_bytes: int = 2,
                 cache_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) a prefill of ``length`` tokens needs: the expanded
    causal attention and the KDA recurrence, the weights (experts as hit by
    its tokens), its latent rows, KDA states and conv tails written."""
    return prefill_flops(c, length), \
        _weight_bytes(c, length, weight_bytes) + \
        _recurrent_bytes(c, cache_bytes) + \
        _latent_row(c, cache_bytes) * length


def tokens(c: dict, rng: np.random.Generator, length: int) -> list[int]:
    return rng.integers(0, c["vocab_size"], length).tolist()
