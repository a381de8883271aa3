"""The Kimi-Linear-48B-A3B cell: a CPU rehearsal at tiny sizes ending
``correct``, an altered served token and the fp8 control caught, the
reference's arithmetic worked by hand, the configuration file against the
program's registry entry, and the weights of the served cut."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

import rehearse

from bench.lib import harness

CELL = "kimi-linear-48b-a3b.serve_reason_b128"
PATH = os.path.join(harness.BENCH, "configs", "kimi-linear-48b-a3b")


def load(tiny=True):
    return (harness.merged(harness.load_json(PATH + ".json"), tiny),
            harness.load_module(PATH + ".py"))


def test_kimi_linear_rehearsal(tmp_path):
    rc, lines, last, err = rehearse.run(rehearse.cell_args(CELL), tmp_path)
    assert rc == 0, err[-4000:]
    assert last["correct"] is True and last["attempted"] > 20
    assert set(last["metrics"]) == {"ttft_p95_s", "tpot_p95_s", "setup_s"}
    assert last["checks"]["never_done"]["value"] == 0
    assert any('"phase": "gaps"' in ln for ln in lines)


def test_kimi_linear_altered_token_is_caught(tmp_path):
    rc, _, last, err = rehearse.run(rehearse.cell_args(CELL), tmp_path,
                                    fault="token_altered")
    assert rc == 0, err[-4000:]
    gap = last["checks"]["logit_gap"]
    assert last["correct"] is False and gap["value"] > gap["limit"]


def test_kimi_linear_control_fails_the_gap(tmp_path):
    """The reference in fp8 in the program's place reads a mean gap above
    the tiny limit, where the program (float32 there) reads within it."""
    path = os.path.join(harness.BENCH, "traffic", "serve_reason_b128.json")
    limit = harness.merged(harness.load_json(path), True)["limits"]["logit_gap"]
    rc, lines, _, err = rehearse.run(
        ["--workload", CELL, "--seeds", "5", "--seconds", "3", "--tiny"],
        tmp_path, tool="tools/readings.py")
    assert rc == 0, err[-4000:]
    rows = [json.loads(ln) for ln in lines if '"variant"' in ln]
    assert rows and all(r["logit_gap"] > limit >= r["program_logit_gap"]
                        for r in rows)


def test_kimi_linear_decode_and_prefill_by_hand():
    """Tiny sizes: D 64; layers KDA (dense MLP 128), KDA, KDA, MLA; KDA 4
    heads of 16, conv 4; MLA 4 heads, q·k 16 + 8, v 16, latent 32; 4 of 8
    experts of 32 held, top 2, shared 32; vocab 256."""
    c, m = load()
    kda = 64 * 3 * 64 + 4 * 3 * 64 + 2 * (64 * 16 + 16 * 64) + 64 * 4 \
        + 64 * 64                                                   # 21504
    mla = 64 * 4 * 24 + 64 * 40 + 2 * 32 * 4 * 16 + 4 * 16 * 64      # 16896
    dense = 3 * 64 * 128
    moe = 64 * 8 + 3 * 64 * 32                    # router, shared expert
    expert = 3 * 64 * 32
    # per token: each matrix once, its expected 2 x 4/8 held experts, the
    # head; 7 FLOPs an element of each KDA layer's 4 x 16 x 16 state
    mats = 3 * kda + mla + dense + 3 * (moe + 2 * 4 / 8 * expert) + 64 * 256
    per_token = 2 * mats + 3 * 7 * 1024
    flops, nbytes = m.decode_cost(c, [10, 20])
    # absorbed: 2 x 4 heads x (40 + 32) a live position
    assert flops == pytest.approx(2 * per_token + 576 * 30)
    norms = 2 * 64
    layers = (norms + kda + 84 + dense) + 2 * (norms + kda + 84 + moe + 8) \
        + (norms + mla + 32 + moe + 8)      # 84: dt_bias, A_log, o_norm
    hit = 4 * (1 - 0.75 ** 2)               # distinct held experts 2 rows hit
    weights = (layers + 3 * hit * expert + 64 + 64 * 256 + 2 * 64) * 2
    # a row's 3 float32 states and bf16 conv tails (3 x 3 x 64), read and
    # written; its 40-wide latent rows read and one written
    recurrent = 3 * (1024 * 4 + 3 * 3 * 64 * 2)
    assert nbytes == pytest.approx(weights + 2 * 2 * recurrent
                                   + 40 * 2 * (30 + 2))
    # expanded: 2 x 4 heads x (24 + 16) a position
    pflops, pbytes = m.prefill_cost(c, 3)
    assert pflops == pytest.approx(3 * per_token + 320 * 6)
    assert pflops == pytest.approx(sum(m.token_flops(c, i + 1)
                                       for i in range(3)))
    hit = 4 * (1 - 0.75 ** 3)
    assert pbytes == pytest.approx(
        (layers + 3 * hit * expert + 64 + 64 * 256 + 3 * 64) * 2
        + recurrent + 40 * 2 * 3)


@pytest.mark.parametrize("tiny", [False, True])
def test_kimi_linear_program_follows_the_file(tiny):
    """The program's layer kinds, KDA widths and conv come from its registry
    entry; they are the file's ``linear_attn_config`` (the program's fields
    map only top-level keys)."""
    from repro import configs
    from repro.models import transformer as tfm

    c, m = load(tiny)
    prog = c["program"]
    base = configs.get_smoke(prog["arch"]) if tiny else \
        configs.get(prog["arch"])
    cfg = base.replace(**{f: c[k] for f, k in prog["fields"].items()})
    la = c["linear_attn_config"]
    assert cfg.kda_layers == tuple(la["kda_layers"])
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.conv_width) == \
        (la["num_heads"], la["head_dim"], la["short_conv_kernel_size"])
    assert [k.replace("_dense", "") for k in tfm.layer_kinds(cfg)] == \
        [{"kda": "kda", "mla": "attn"}[k] for k in m.kinds(c)]
    assert (cfg.num_experts, cfg.experts_held) == \
        (c["router_experts"], c["num_experts"])


def test_kimi_linear_weights_in_bfloat16_fit_the_deployment():
    """The served cut at published widths: 2.753 B parameters (5.51 GB in
    bfloat16), counted from shapes without making them."""
    c, m = load(tiny=False)
    shapes = jax.eval_shape(lambda: m.make_weights(c, 0, jnp.bfloat16))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert 2.75e9 < n < 2.76e9
