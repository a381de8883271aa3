"""The Moonlight-16B-A3B cell: a CPU rehearsal at tiny sizes ending
``correct``, an altered served token and the fp8 control caught, the mean
gap its driver compares, the reference's arithmetic worked by hand, the
reference itself against plainer forms of it, and the prefill roofline's
reader."""

import json
import os
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rehearse

from bench.lib import harness, lowp

CELL = "moonlight-16b-a3b.serve_chat_b64"


def load():
    path = os.path.join(harness.BENCH, "configs", "moonlight-16b-a3b")
    return (harness.merged(harness.load_json(path + ".json"), True),
            harness.load_module(path + ".py"))


def test_moonlight_rehearsal(tmp_path):
    rc, lines, last, err = rehearse.run(rehearse.cell_args(CELL), tmp_path)
    assert rc == 0, err[-4000:]
    assert last["correct"] is True and last["attempted"] > 20
    assert set(last["metrics"]) == {"ttft_p95_s", "tpot_p95_s", "setup_s"}
    assert last["checks"]["never_done"]["value"] == 0
    assert any('"phase": "generator"' in ln for ln in lines)


def test_moonlight_altered_token_is_caught(tmp_path):
    rc, _, last, err = rehearse.run(rehearse.cell_args(CELL), tmp_path,
                                    fault="token_altered")
    assert rc == 0, err[-4000:]
    gap = last["checks"]["logit_gap"]
    assert last["correct"] is False and gap["value"] > gap["limit"]


def test_moonlight_control_fails_the_gap(tmp_path):
    """The reference in fp8 in the program's place reads a mean gap above
    the tiny limit, where the program (float32 there) reads within it."""
    path = os.path.join(harness.BENCH, "traffic", "serve_chat_b64.json")
    limit = harness.merged(harness.load_json(path), True)["limits"]["logit_gap"]
    rc, lines, _, err = rehearse.run(
        ["--workload", CELL, "--seeds", "5", "--seconds", "3", "--tiny"],
        tmp_path, tool="tools/readings.py")
    assert rc == 0, err[-4000:]
    rows = [json.loads(ln) for ln in lines if '"variant"' in ln]
    assert rows and all(r["logit_gap"] > limit >= r["program_logit_gap"]
                        for r in rows)
    gaps = [json.loads(ln) for ln in lines if '"phase": "gaps"' in ln]
    assert gaps and gaps[0]["control"]["mean"] == rows[0]["logit_gap"]


def test_routed_driver_reads_the_mean_gap():
    """Per position: 0 where the served token is the reference's best, else
    how far below it; the check reads their mean over every served token."""
    drv = harness.load_module(os.path.join(harness.BENCH, "drivers",
                                           "serve_routed.py"))
    rows = np.array([[0.0, 2.0, 1.0], [3.0, 0.5, 1.0], [1.0, 1.0, 4.0]])
    gaps = drv.position_gaps(rows, [1, 2, 2])
    np.testing.assert_array_equal(gaps, [0.0, 2.0, 0.0])
    s = drv.summary([gaps, drv.position_gaps(rows[:1], [0])])
    assert s["tokens"] == 4 and s["max"] == 2.0
    assert s["mean"] == pytest.approx(1.0) and s["missed"] == 0.5


def test_moonlight_decode_and_prefill_by_hand():
    """Tiny sizes: D 64, 4 heads, q·k 16 + 8, v 16, latent 32, dense d_ff
    128, 8 experts of 32, top 2, shared 64, 1 dense + 2 MoE layers, vocab
    256."""
    c, m = load()
    attn = 64 * 4 * 24 + 64 * 40 + 2 * 32 * 4 * 16 + 4 * 16 * 64      # 16896
    dense = attn + 3 * 64 * 128                                       # 41472
    moe = attn + 64 * 8 + 3 * 64 * 64                                 # 29696
    expert = 3 * 64 * 32
    per_token = 2 * (dense + 2 * (moe + 2 * expert) + 64 * 256)
    flops, nbytes = m.decode_cost(c, [10, 20])
    # absorbed: 2 x 4 heads x (40 + 32) a position and layer
    assert flops == 2 * per_token + 3 * 576 * 30
    vectors = 2 * 64 + 32
    hit = 8 * (1 - 0.75 ** 2)              # distinct experts two rows hit
    weights = (dense + vectors + 2 * (moe + vectors + 8 + hit * expert)
               + 64 + 64 * 256 + 2 * 64) * 2
    assert nbytes == pytest.approx(weights + 3 * 40 * 2 * (30 + 2))
    # expanded: 2 x 4 heads x (24 + 16) a position and layer
    pflops, pbytes = m.prefill_cost(c, 3)
    assert pflops == 3 * per_token + 3 * 320 * 6
    assert pflops == m.prefill_flops(c, 3) == sum(
        m.token_flops(c, i + 1) for i in range(3))
    hit = 8 * (1 - 0.75 ** 3)
    assert pbytes == pytest.approx(
        (dense + vectors + 2 * (moe + vectors + 8 + hit * expert)
         + 64 + 64 * 256 + 3 * 64) * 2 + 3 * 40 * 2 * 3)


def test_moonlight_reference_is_causal():
    c, m = load()
    params = m.make_weights(c, 1, jnp.float32)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 256, 12), jnp.int32)
    full = m.logits(params, toks, c, lowp.F32)
    head = m.logits(params, toks[:7], c, lowp.F32)
    np.testing.assert_allclose(np.asarray(full[:7]), np.asarray(head),
                               rtol=1e-5, atol=1e-5)


def test_moonlight_dense_experts_equal_a_loop_over_the_chosen():
    """Every expert on every token, gated by zero where not chosen, equals a
    per-token loop over its top k (in float64 numpy), the shared experts
    added."""
    c, m = load()
    params = m.make_weights(c, 2, jnp.float32)
    p = {k: np.asarray(v[0], np.float64) for k, v in params["layers"].items()}
    h = np.random.default_rng(3).standard_normal((6, 64))
    got = np.asarray(m._experts(jnp.asarray(h, jnp.float32),
                                {k: jnp.asarray(v, jnp.float32)
                                 for k, v in p.items()}, c, lowp.F32))

    def swiglu(x, g, u, d):
        a, b = x @ g, x @ u
        return (a / (1 + np.exp(-a)) * b) @ d

    for s in range(6):
        scores = 1 / (1 + np.exp(-(h[s] @ p["router"])))
        sel = np.argsort(-(scores + p["router_bias"]))[:2]
        gates = scores[sel] / scores[sel].sum() * c["routed_scaling_factor"]
        want = sum(g * swiglu(h[s], p["we_gate"][e], p["we_up"][e],
                              p["we_down"][e]) for g, e in zip(gates, sel))
        want += swiglu(h[s], p["shared_gate"], p["shared_up"],
                       p["shared_down"])
        np.testing.assert_allclose(got[s], want, rtol=1e-4, atol=1e-5)


def test_prefill_roofline_over_the_calls_device_time():
    c, m = load()
    steps = [(0.5, [8], []), (1.5, [16, 8], [10]), (9.0, [32], [40])]
    tf = NS(busy_s=0.5, module=lambda name: (3.0, 0.03)
            if name == "prefill_step" else (0.0, 0.0))
    cell = NS(config=c, model=m, trace_facts=tf,
              peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11},
              facts={"trace_start": 1.0, "trace_stop": 3.0, "steps": steps})
    reader = harness.load_module(os.path.join(
        harness.BENCH, "metrics", "prefill_step_roofline.py"))
    least = [max(f / 1e12, b / 1e11) for f, b in
             (m.prefill_cost(c, 16), m.prefill_cost(c, 8))]
    assert reader.read(cell) == pytest.approx(100 * np.mean(least) / 0.01)
    cell.model = NS()                       # a reference with no prefill_cost
    assert reader.read(cell) is None
    cell.model, cell.trace_facts = m, None
    assert reader.read(cell) is None


def test_moonlight_weights_in_bfloat16_fit_the_deployment():
    """The served stage at published widths: 3.09 B parameters (6.19 GB in
    bfloat16), counted from shapes without making them."""
    raw = harness.load_json(os.path.join(harness.BENCH, "configs",
                                         "moonlight-16b-a3b.json"))
    _, m = load()
    shapes = jax.eval_shape(lambda: m.make_weights(raw, 0, jnp.bfloat16))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert 3.08e9 < n < 3.10e9
