"""FLOP and byte counts against hand counts at the tiny configurations, and
the plain references against plainer forms of themselves."""

import os

import jax.numpy as jnp
import numpy as np

from bench.lib import harness, lowp

CONFIGS = os.path.join(harness.BENCH, "configs")


def load(name):
    cfg = harness.merged(harness.load_json(os.path.join(CONFIGS, name + ".json")),
                         True)
    return cfg, harness.load_module(os.path.join(CONFIGS, name + ".py"))


def test_granite_decode_and_prefill_by_hand():
    c, m = load("granite-8b")
    layer = 64 + 64 * 4 * 16 + 2 * 64 * 2 * 16 + 4 * 16 * 64 + 64 + 3 * 64 * 128
    assert m.layer_params(c) == layer == 36992
    per_token = 2 * (2 * (layer - 128) + 64 * 256)
    attend = 4 * 2 * 4 * 16
    flops, nbytes = m.decode_cost(c, [10, 20])
    assert flops == 2 * per_token + attend * 30
    weights = (2 * layer + 64 + 64 * 256) * 2 + 2 * 64 * 2
    assert nbytes == weights + 2 * 2 * 2 * 16 * 2 * (30 + 2)
    assert m.prefill_flops(c, 3) == sum(m.token_flops(c, i + 1) for i in range(3))


def test_granite_reference_is_causal():
    """Logits at a position ignore later tokens, so a served sequence can be
    padded to a bucket."""
    c, m = load("granite-8b")
    params = m.make_weights(c, 1, jnp.float32)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 256, 12), jnp.int32)
    full = m.logits(params, toks, c, lowp.F32)
    head = m.logits(params, toks[:7], c, lowp.F32)
    np.testing.assert_allclose(np.asarray(full[:7]), np.asarray(head),
                               rtol=1e-5, atol=1e-5)


def test_fp8_rounding_grid():
    x = jnp.asarray([448.0, 1.0, 1.0625, 1.0625 + 1e-3, -0.3, 1e-9, 0.0])
    q = np.asarray(lowp.round_fp8(x))
    assert q[0] == 448.0 and q[1] == 1.0 and q[-1] == 0.0
    # three mantissa bits: the step above 1 (after scaling) is 1/8
    y = np.asarray(lowp.round_fp8(jnp.linspace(-3.0, 3.0, 1001)))
    scale = 3.0 / 448.0
    rel = np.abs(y - np.linspace(-3, 3, 1001)) / np.maximum(
        np.abs(np.linspace(-3, 3, 1001)), 2.0 ** -6 * scale)
    assert rel.max() <= 2.0 ** -4 + 1e-6
