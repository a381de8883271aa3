"""Latent attention and dropless experts (moonshot-v1-16b-a3b, the
Moonlight-16B-A3B block) against the plain float32 reference the benchmark
checks the chip with (``bench/configs/moonlight-16b-a3b.py``), on seeded
random weights at the reference's tiny sizes, in float32.

The program and the reference sum the same products in other orders (the
absorbed decode regroups the attention's products, the grouped expert
products add an expert's rows in another order), so in float32 they differ
by rounding alone: about 1e-6 of logits of size ~1. The tolerances below
leave an order of magnitude above that and stay far below what a wrong
expert, a wrong gate or a misplaced rope (differences of 1e-2 and more)
gives.
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
from repro.models import model as M
from repro.models import moe as moe_mod
from repro.models.layers import init_tree
from repro.parallel import sharding as shd
from repro.serve.engine import ServeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "bench", "configs", "moonlight-16b-a3b")
TOL = 1e-4          # float32 rounding, ~1e-6, with room
SCOPES = ("mla.latent", "mla.attend", "moe.route", "moe.experts",
          "moe.shared")


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("moonlight_ref", CONFIG + ".py")
F32 = _load("bench_lowp", os.path.join(ROOT, "bench", "lib", "lowp.py")).F32


@pytest.fixture(scope="module")
def setup():
    """(reference config, program config, weights in the program's
    layout), as the benchmark's tiny run builds them."""
    raw = json.load(open(CONFIG + ".json"))
    c = {**raw, **raw["tiny"]}
    fields = {f: c[k] for f, k in raw["program"]["fields"].items()}
    cfg = configs.get_smoke(raw["program"]["arch"]).replace(**fields)
    assert cfg.dtype == "float32" and cfg.first_dense_layers == 1
    return c, cfg, REF.make_weights(c, 7, jnp.float32)


def _ref_logits(c, params, toks):
    return np.asarray(jax.jit(lambda p, t: REF.logits(p, t, c, F32))(
        params, jnp.asarray(toks, jnp.int32)))


def test_prefill_logits_match_reference(setup):
    c, cfg, params = setup
    toks = np.random.default_rng(1).integers(0, cfg.vocab_size, 32)
    ref = _ref_logits(c, params, toks)
    for n in (5, 17, 32):
        logits, _ = M.prefill(params, cfg, {"tokens": jnp.asarray(toks[None, :n])},
                              64)
        np.testing.assert_allclose(np.asarray(logits[0]), ref[n - 1],
                                   rtol=TOL, atol=TOL)


def test_decode_at_different_depths_matches_reference(setup):
    """Rows prefilled to different depths, spliced into one batched cache,
    decode 4 steps; each step's logits are the reference's at that row's
    position."""
    c, cfg, params = setup
    lengths, steps, max_len = (4, 13, 29), 4, 40
    seqs = np.random.default_rng(2).integers(0, cfg.vocab_size, (3, max_len))
    mesh = make_mesh(jax.devices()[:1], (1, 1))
    engine = ServeEngine(cfg, mesh, shd.make_rules(multi_pod=False), params,
                         max_batch=3, max_len=max_len)
    prefill = jax.jit(lambda p, t: M.prefill(p, cfg, {"tokens": t}, max_len))
    for b, n in enumerate(lengths):
        _, row = prefill(params, jnp.asarray(seqs[b:b + 1, :n]))
        engine._splice(row, b)
    refs = [_ref_logits(c, params, seqs[b]) for b in range(3)]
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


def _absorbed_gap(cfg, dtype) -> float:
    """Largest difference, relative to the output's scale, between the
    absorbed decode of the last token (over a latent cache built by the
    prefill path, all in ``dtype``) and the float32 expanded attention of
    the same token."""
    p = init_tree(attn.mla_specs(cfg), jax.random.PRNGKey(3), jnp.float32)
    S, Smax = 12, 16
    x = jax.random.normal(jax.random.PRNGKey(4), (2, S, cfg.d_model))
    want, _ = attn.mla_apply(p, x, cfg)
    pd = jax.tree_util.tree_map(lambda a: a.astype(dtype), p)
    xd = x.astype(dtype)
    _, rows = attn.mla_apply(pd, xd[:, :S - 1], cfg)
    cache = jnp.pad(rows, ((0, 0), (0, Smax - S + 1), (0, 0)))
    got, _ = attn.mla_decode(pd, xd[:, S - 1:], cache, jnp.int32(S - 1), cfg)
    scale = float(jnp.max(jnp.abs(want[:, -1])))
    return float(jnp.max(jnp.abs(got[:, 0].astype(jnp.float32)
                                 - want[:, -1]))) / scale


def test_absorbed_decode_matches_expanded_form(setup):
    """In float32 the two forms differ by rounding (~1e-6 of the output's
    scale); the same decode in bfloat16 misses by ~1e-2, so the tolerance
    separates a float32 program from one a precision below."""
    _, cfg, _ = setup
    assert _absorbed_gap(cfg, jnp.float32) < TOL
    assert _absorbed_gap(cfg, jnp.bfloat16) > TOL


def test_dropless_routing_under_a_skewed_router(setup):
    """A choice bias that sends every token to expert 0: no token loses an
    expert on the serving path, whose result is the reference's dense sum
    over all experts; the training path's capacity dispatch drops."""
    c, cfg, params = setup
    p = {k: v[0] for k, v in params["layers"].items()}
    p["router_bias"] = p["router_bias"].at[0].set(10.0)
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 16, cfg.d_model))
    served = moe_mod.moe_serve(p, h, cfg)
    dense = jnp.stack([REF._experts(h[b], p, c, F32) for b in range(2)])
    np.testing.assert_allclose(np.asarray(served), np.asarray(dense),
                               rtol=TOL, atol=TOL)
    _, sel, _ = moe_mod.route(p, h, cfg)
    assert bool(jnp.all(jnp.any(sel == 0, axis=-1)))
    trained, _ = moe_mod.moe_apply(p, h, cfg)
    assert float(jnp.max(jnp.abs(trained - dense))) > 1e-2


def test_choice_bias_moves_the_choice_not_the_gates(setup):
    _, cfg, params = setup
    p = {k: v[0] for k, v in params["layers"].items()}
    h = jax.random.normal(jax.random.PRNGKey(6), (64, cfg.d_model))
    gates, sel, _ = moe_mod.route(p, h, cfg)
    _, sel0, _ = moe_mod.route({**p, "router_bias": 0 * p["router_bias"]},
                               h, cfg)
    assert bool(jnp.any(jnp.sort(sel, -1) != jnp.sort(sel0, -1)))
    scores = jax.nn.sigmoid(h @ p["router"])
    chosen = jnp.take_along_axis(scores, sel, axis=-1)
    np.testing.assert_allclose(
        np.asarray(gates),
        np.asarray(chosen / chosen.sum(-1, keepdims=True) * cfg.routed_scaling),
        rtol=1e-6, atol=1e-6)


def test_decode_hlo_carries_the_named_scopes(setup):
    _, cfg, params = setup
    cache = M.init_cache(cfg, 2, 16)
    text = jax.jit(lambda p, c, t, q: M.decode_step(p, cfg, c, t, q)).lower(
        params, cache, jnp.zeros((2, 1), jnp.int32),
        jnp.zeros((2,), jnp.int32)).compile().as_text()
    assert all(scope in text for scope in SCOPES)


def test_splice_fills_one_row_of_each_stacked_group(setup):
    """A batch-1 prefill lands in row b of both the dense group (1 layer)
    and the MoE group (num_layers - 1 layers); every other row is
    unchanged. The cache's shardings place the batch on the same axis."""
    _, cfg, params = setup
    B, b, max_len = 4, 2, 24
    mesh = make_mesh(jax.devices()[:1], (1, 1))
    engine = ServeEngine(cfg, mesh, shd.make_rules(multi_pod=False), params,
                         max_batch=B, max_len=max_len)
    leaves, tree = jax.tree_util.tree_flatten(engine.cache)
    keys = jax.random.split(jax.random.PRNGKey(8), len(leaves))
    engine.cache = before = jax.tree_util.tree_unflatten(
        tree, [jax.random.normal(k, x.shape) for k, x in zip(keys, leaves)])
    toks = jnp.asarray(np.arange(9)[None] % cfg.vocab_size, jnp.int32)
    _, row = M.prefill(params, cfg, {"tokens": toks}, max_len)
    engine._splice(row, b)
    assert before["dense_layers"]["latent"].shape[0] == 1
    assert before["layers"]["latent"].shape[0] == cfg.num_layers - 1
    for group in ("dense_layers", "layers"):
        got, old = engine.cache[group]["latent"], before[group]["latent"]
        np.testing.assert_array_equal(np.asarray(got[:, b]),
                                      np.asarray(row[group]["latent"][:, 0]))
        others = [i for i in range(B) if i != b]
        np.testing.assert_array_equal(np.asarray(got[:, others]),
                                      np.asarray(old[:, others]))
    pspecs = shd.cache_pspecs(M.cache_shapes(cfg, B, max_len),
                              shd.make_rules(multi_pod=False), mesh, cfg)
    for group in ("dense_layers", "layers"):
        assert tuple(pspecs[group]["latent"])[:2] == (None, "data")
