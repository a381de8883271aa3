"""Kimi Delta Attention, NoPE latent attention and experts held by share
(kimi-linear-48b-a3b, the Kimi-Linear-48B-A3B block) against the plain
float32 reference the benchmark checks the chip with
(``bench/configs/kimi-linear-48b-a3b.py``), on seeded random weights at the
reference's tiny sizes, in float32.

The program and the reference sum the same products in other orders (the
chunked delta rule solves a chunk's updates at once where the reference
steps token by token, the grouped expert products add an expert's rows in
another order), so in float32 they differ by rounding alone: about 1e-6 of
values of size ~1. ``TOL`` leaves an order of magnitude above that and
stays far below what a wrong decay, a missed update, a state carried wrong
or a wrong expert gives (1e-2 and more).
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch.mesh import make_mesh
from repro.models import attention as attn
from repro.models import kda
from repro.models import model as M
from repro.models import moe as moe_mod
from repro.models.layers import init_tree
from repro.parallel import sharding as shd
from repro.serve.engine import ServeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "bench", "configs", "kimi-linear-48b-a3b")
TOL = 1e-4          # float32 rounding, ~1e-6, with room
RULES = shd.make_rules(multi_pod=False)


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("kimi_linear_ref", CONFIG + ".py")
F32 = _load("bench_lowp", os.path.join(ROOT, "bench", "lib", "lowp.py")).F32


def _tiny():
    """(reference config, program config) at the benchmark's tiny sizes."""
    raw = json.load(open(CONFIG + ".json"))
    c = {**raw, **raw["tiny"],
         "linear_attn_config": {**raw["linear_attn_config"],
                                **raw["tiny"]["linear_attn_config"]}}
    fields = {f: c[k] for f, k in raw["program"]["fields"].items()}
    return c, configs.get_smoke(raw["program"]["arch"]).replace(**fields)


@pytest.fixture(scope="module")
def setup():
    """(reference config, program config, weights in the program's
    layout), as the benchmark's tiny run builds them."""
    c, cfg = _tiny()
    assert cfg.dtype == "float32" and cfg.experts_held < cfg.num_experts
    return c, cfg, REF.make_weights(c, 7, jnp.float32)


BUCKET = 128        # the reference runs every sequence padded to this


@pytest.fixture(scope="module")
def ref_logits(setup):
    """The reference's logits (n, V) of a sequence of n <= ``BUCKET``
    tokens, run padded (it is causal) so that one program serves all."""
    c, _, params = setup
    run = jax.jit(lambda t: REF.logits(params, t, c, F32))

    def logits(toks):
        toks = np.asarray(toks)
        padded = np.pad(toks, (0, BUCKET - len(toks)))
        return np.asarray(run(jnp.asarray(padded, jnp.int32)))[:len(toks)]
    return logits


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(jax.devices()[:1], (1, 1))


def _ref_gap(ref_logits, req) -> float:
    """The widest gap by which a served token's reference logit lies below
    the reference's best at its position."""
    seq = req.prompt + req.generated[:-1]
    rows = ref_logits(seq)[len(req.prompt) - 1:]
    best = rows.max(-1)
    return float(np.max(best - rows[np.arange(len(rows)), req.generated]))


# --------------------------------------------------------------- delta rule
def _delta_inputs(seed, B, S, H=3, K=8, V=8):
    """Random delta-rule inputs: q, k normed, decays mild enough that a
    chunk's state reaches the next (log α in [-0.3, 0]), β in (0, 1), and
    a state carried in."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (B, S, H, K))) * K ** -0.5
    k = unit(jax.random.normal(ks[1], (B, S, H, K)))
    v = jax.random.normal(ks[2], (B, S, H, V))
    g = -0.3 * jax.random.uniform(ks[3], (B, S, H, K))
    beta = jax.random.uniform(ks[4], (B, S, H))
    state = jax.random.normal(ks[5], (B, H, K, V))
    return q, k, v, g, beta, state


@pytest.mark.parametrize("S,chunk", [(21, 8), (100, 64), (64, 64), (5, 8)])
def test_chunked_delta_rule_equals_the_recurrence(S, chunk):
    """Lengths that are and are not whole chunks, one chunk or several, a
    state carried in: the chunked form's outputs and final state are the
    reference's token-by-token ones, and so are the decode's one-token
    steps."""
    q, k, v, g, beta, state = _delta_inputs(S, 2, S)
    want_o, want_s = jax.vmap(REF.delta_rule)(q, k, v, g, beta, state)
    o, s = kda.delta_chunked(q, k, v, g, beta, state, chunk=chunk)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                               rtol=TOL, atol=TOL)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s),
                               rtol=TOL, atol=TOL)
    step_s, one = state, jax.jit(kda.delta_step)
    for t in range(S):
        step_o, step_s = one(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t],
                             step_s)
        np.testing.assert_allclose(np.asarray(step_o),
                                   np.asarray(want_o[:, t]),
                                   rtol=TOL, atol=TOL)
    np.testing.assert_allclose(np.asarray(step_s), np.asarray(want_s),
                               rtol=TOL, atol=TOL)


def test_chunk_padding_leaves_the_state_alone():
    """A prompt padded to a whole chunk with β = 0 and log α = 0 ends in
    the state its real tokens leave, and strong decays (log α down to -40
    a token, e^(G_i − G_j) far below float32's range) stay finite."""
    q, k, v, g, beta, state = _delta_inputs(3, 1, 37)
    g = g * 130.0
    o, s = kda.delta_chunked(q, k, v, g, beta, state, chunk=16)
    want_o, want_s = jax.vmap(REF.delta_rule)(q, k, v, g, beta, state)
    assert np.all(np.isfinite(np.asarray(o)))
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s),
                               rtol=TOL, atol=TOL)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                               rtol=TOL, atol=TOL)


def test_kda_layer_matches_reference(setup):
    """The whole KDA mixer (projections, conv, decay, gated norm) over
    prompts of 5 to 100 tokens, one and two chunks."""
    c, cfg, params = setup
    p = params["layers"]["layer_1"]
    h = jax.random.normal(jax.random.PRNGKey(4), (100, cfg.d_model))
    want = REF._kda(h, p, c, F32)
    for n in (5, 64, 100):
        got, cache = kda.kda_apply(p, h[None, :n], cfg)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[:n]),
                                   rtol=TOL, atol=TOL)
        assert cache["conv"].shape[1] == cfg.conv_width - 1


# ------------------------------------------------------------------ serving
def test_prefill_logits_match_reference(setup, ref_logits):
    _, cfg, params = setup
    toks = np.random.default_rng(1).integers(0, cfg.vocab_size, 100)
    ref = ref_logits(toks)
    prefill = jax.jit(lambda t: M.prefill(params, cfg, {"tokens": t}, 128))
    for n in (5, 100):
        logits, _ = prefill(jnp.asarray(toks[None, :n]))
        np.testing.assert_allclose(np.asarray(logits[0]), ref[n - 1],
                                   rtol=TOL, atol=TOL)


def test_decode_at_different_depths_matches_reference(setup, mesh,
                                                      ref_logits):
    """Rows prefilled to depths on both sides of a chunk through the
    engine's own prefill step, spliced into one batched cache, decode 4
    steps; each step's logits are the reference's at that row's
    position."""
    _, cfg, params = setup
    lengths, steps, max_len = (4, 70, 29), 4, 80
    seqs = np.random.default_rng(2).integers(0, cfg.vocab_size, (3, max_len))
    engine = ServeEngine(cfg, mesh, RULES, params, max_batch=3,
                         max_len=max_len)
    for b, n in enumerate(lengths):
        _, row = engine._prefill_fn(n)(
            params, {"tokens": jnp.asarray(seqs[b:b + 1, :n])})
        engine._splice(row, b)
    refs = [ref_logits(seqs[b]) for b in range(3)]
    pos = np.array(lengths, np.int32)
    cache = engine.cache
    decode = jax.jit(lambda p, c, t, q: M.decode_step(p, cfg, c, t, q))
    for _ in range(steps):
        tok = jnp.asarray(seqs[np.arange(3), pos][:, None])
        logits, cache = decode(params, cache, tok, jnp.asarray(pos))
        for b in range(3):
            np.testing.assert_allclose(np.asarray(logits[b]), refs[b][pos[b]],
                                       rtol=TOL, atol=TOL)
        pos = pos + 1


def test_engine_serves_the_reference_tokens(setup, mesh, ref_logits):
    """More requests than slots through ``ServeEngine``: every served token
    is the reference's best at its position, to float32 rounding (a near
    tie may go either way within ``TOL``)."""
    _, cfg, params = setup
    rng = np.random.default_rng(3)
    lengths, budgets = (9, 70, 3, 33, 66, 12), (7, 5, 12, 4, 9, 6)
    with mesh:
        engine = ServeEngine(cfg, mesh, RULES, params, max_batch=3,
                             max_len=96)
        for n, k in zip(lengths, budgets):
            engine.submit(rng.integers(0, cfg.vocab_size, n).tolist(),
                          max_new_tokens=k)
        done = engine.run()
    assert [len(r.generated) for r in done] == list(budgets)
    assert max(_ref_gap(ref_logits, r) for r in done) <= TOL


def test_idle_rows_leave_no_trace(setup, mesh, ref_logits):
    """A cache full of garbage (states of 1e3, conv tails and latent rows
    at random), and a row that idles, decoding token 0 over its finished
    request's state, while another row decodes a long answer: the requests
    admitted into those rows later serve the reference's tokens, since the
    splice replaces a row's state and conv tails whole."""
    _, cfg, params = setup
    rng = np.random.default_rng(4)
    with mesh:
        engine = ServeEngine(cfg, mesh, RULES, params, max_batch=2,
                             max_len=96)
        leaves, tree = jax.tree_util.tree_flatten(engine.cache)
        keys = jax.random.split(jax.random.PRNGKey(9), len(leaves))
        engine.cache = jax.tree_util.tree_unflatten(tree, [
            1e3 * jax.random.normal(k, x.shape, x.dtype)
            for k, x in zip(keys, leaves)])
        first = [engine.submit(rng.integers(0, cfg.vocab_size, 40).tolist(),
                               max_new_tokens=30),
                 engine.submit(rng.integers(0, cfg.vocab_size, 7).tolist(),
                               max_new_tokens=2)]
        for _ in range(12):                    # row 1 idles after 2 tokens
            engine.step()
        assert engine.requests[first[1]].done
        assert not engine.requests[first[0]].done
        later = engine.submit(rng.integers(0, cfg.vocab_size, 21).tolist(),
                              max_new_tokens=8)
        done = engine.run()
    assert all(r.done for r in done)
    for rid in (*first, later):
        assert _ref_gap(ref_logits, engine.requests[rid]) <= TOL


def test_nope_latent_attention_ignores_positions(setup):
    """Nothing in Kimi's latent attention is rotated, so the decode of a
    token over a full ring of latent rows reads the same at every lap of
    the ring; the roped form (Moonlight's) reads otherwise."""
    _, cfg, _ = setup
    p = init_tree(attn.mla_specs(cfg), jax.random.PRNGKey(3), jnp.float32)
    S = 13                                        # the ring's length
    x = jax.random.normal(jax.random.PRNGKey(4), (2, S, cfg.d_model))

    def decoded(c, laps):
        _, rows = attn.mla_apply(p, x[:, :S - 1], c)
        cache = jnp.pad(rows, ((0, 0), (0, 1), (0, 0)))
        pos = jnp.full((2,), S - 1 + laps * S, jnp.int32)
        return np.asarray(attn.mla_decode(p, x[:, S - 1:], cache, pos, c)[0])

    base = decoded(cfg, 0)
    for laps in (1, 5):
        np.testing.assert_allclose(decoded(cfg, laps), base,
                                   rtol=TOL, atol=TOL)
    roped = cfg.replace(mla_nope=False)
    assert np.max(np.abs(decoded(roped, 1) - decoded(roped, 0))) > 1e-2


def test_named_scopes_in_the_programs(setup):
    """``kda.*`` beside ``mla.*`` and ``moe.*`` in the decode, and the
    chunked form's scope in the prefill."""
    _, cfg, params = setup
    cache = M.init_cache(cfg, 2, 16)
    decode = jax.jit(lambda p, c, t, q: M.decode_step(p, cfg, c, t, q)).lower(
        params, cache, jnp.zeros((2, 1), jnp.int32),
        jnp.zeros((2,), jnp.int32)).compile().as_text()
    assert all(s in decode for s in ("kda.project", "kda.recur", "kda.out",
                                     "mla.latent", "mla.attend", "moe.route",
                                     "moe.experts", "moe.shared"))
    prefill = jax.jit(lambda p, t: M.prefill(p, cfg, {"tokens": t}, 16)).lower(
        params, jnp.zeros((1, 9), jnp.int32)).compile().as_text()
    assert all(s in prefill for s in ("kda.project", "kda.chunk", "kda.out"))


# ------------------------------------------------------- experts by share
SHARES = 8


def _uncut(c):
    """A tiny expert layer of 16 experts, all held (the uncut layer)."""
    c = {**c, "router_experts": 16, "num_experts": 16,
         "num_experts_per_token": 4}
    return c, REF.make_weights(c, 11, jnp.float32)["layers"]["layer_1"]


def _held(cfg, p, experts):
    """The program's expert layer holding ``experts`` (a share of the
    router's 16): relabelled so that they come first, as the layer holds
    experts 0..n-1, the router's columns and choice bias with them."""
    rest = [e for e in range(p["router"].shape[1]) if e not in experts]
    perm = np.asarray(list(experts) + rest)
    q = {**p, "router": p["router"][:, perm],
         "router_bias": p["router_bias"][perm]}
    for name in ("we_gate", "we_up", "we_down"):
        q[name] = p[name][np.asarray(experts)]
    return cfg.replace(num_experts=16, experts_held=len(experts),
                       num_experts_per_tok=4), q


def test_expert_shares_sum_to_the_uncut_layer(setup):
    """8 chips of 2 experts each: each share's part of one MoE layer,
    routed over all 16, with the shared expert counted once, sums to the
    uncut reference layer's output."""
    c, cfg, _ = setup
    c16, p = _uncut(c)
    h = jax.random.normal(jax.random.PRNGKey(12), (1, 24, cfg.d_model))
    want = REF._experts(h[0], p, c16, F32)
    shared = REF._swiglu(h[0], p["shared_gate"], p["shared_up"],
                         p["shared_down"], F32)
    parts = []
    per = 16 // SHARES
    for s in range(SHARES):
        share_cfg, q = _held(cfg, p, range(s * per, (s + 1) * per))
        parts.append(moe_mod.moe_serve(q, h, share_cfg)[0])
    got = sum(parts) - (SHARES - 1) * shared
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=TOL, atol=TOL)
    # each share alone is far from the whole: the others' experts matter
    assert float(jnp.max(jnp.abs(parts[0] - want))) > 1e-2


def test_held_share_equals_reference_given_the_share(setup):
    """The layer holding experts 0..3 of 16 (one chip's share) computes
    what the reference computes given the same share, in prefill and in
    the layer-stacked decode form."""
    c, cfg, _ = setup
    c16, p = _uncut(c)
    share_cfg, q = _held(cfg, p, range(4))
    c4 = {**c16, "num_experts": 4}
    h = jax.random.normal(jax.random.PRNGKey(13), (1, 24, cfg.d_model))
    want = REF._experts(h[0], q, c4, F32)
    got = moe_mod.moe_serve(q, h, share_cfg)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=TOL, atol=TOL)
    stacked = {k: v[None] if k.startswith("we_") else v for k, v in q.items()}
    got = moe_mod.moe_serve(stacked, h, share_cfg, jnp.int32(0))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=TOL, atol=TOL)
