"""Ragged decode attention (``repro.kernels.decode_attention``) against the
jnp path, the kernel run in interpret mode on the CPU.

The kernel reads each row's first ``min(pos + 1, S)`` slots of one layer of
a stacked cache, block by block; the jnp path reads the layer's whole
cache and masks it by age. Live lengths sit at and around the block edges,
with an inactive row (``pos = 0``) and a wrapped ring buffer among them.
Every slot the kernel must not read (past a row's length, and every other
layer) holds NaN in its cache, so any such read reaches the output.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels.decode_attention import (decode_attention,
                                            grouped_decode_ref,
                                            latent_decode_ref)
from repro.launch.mesh import make_mesh
from repro.models import attention as attn
from repro.models import model as M
from repro.parallel import sharding as shd

L, S, BLK = 3, 64, 16
K, G, DH = 2, 4, 32                  # grouped: 2 KV heads of 4 queries
H, C, R = 4, 32, 16                  # latent: 4 heads over a 48-wide row
# positions -> live lengths 1, BLK-1, BLK, BLK+1, S, 1 (inactive), 38
LENGTHS = [0, BLK - 2, BLK - 1, BLK, S - 1, 0, 37]
# a ring buffer, wrapped (every slot live) beside short rows
RING = [S, S + 5, 3 * S - 1, 2, BLK, 0, 100]
TOL = 2e-2                           # the kernel's value dot is bfloat16


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(jax.devices()[:1], (1, 1))


def _caches(rng, shape, pos, layer, slot_axis):
    """(cache with zeros where the kernel must not read, the same with
    NaN there): slots at or past each row's length in ``layer``, and every
    other layer."""
    cache = rng.standard_normal(shape).astype(np.float32)
    ndim = len(shape) - 1                                      # one layer's
    n = np.minimum(np.asarray(pos) + 1, S).reshape([-1] + [1] * (ndim - 1))
    slots = np.arange(S).reshape([S if i == slot_axis else 1
                                  for i in range(ndim)])
    dead = np.broadcast_to(slots >= n, shape[1:])
    cache[layer][dead] = 0.0
    poisoned = cache.copy()
    poisoned[layer][dead] = np.nan
    poisoned[np.arange(L) != layer] = np.nan
    return (jnp.asarray(cache, jnp.bfloat16),
            jnp.asarray(poisoned, jnp.bfloat16))


@pytest.mark.parametrize("pos,layer", [(LENGTHS, 0), (LENGTHS, L - 1),
                                       (RING, L - 1)],
                         ids=["lengths-first-layer", "lengths-last-layer",
                              "wrapped-ring"])
@pytest.mark.parametrize("layout", ["grouped", "latent"])
def test_kernel_matches_jnp_path(mesh, layout, pos, layer):
    rng = np.random.default_rng(len(pos) * 10 + layer)
    pos_a = jnp.asarray(pos, jnp.int32)
    B = len(pos)
    if layout == "grouped":
        q = jnp.asarray(rng.standard_normal((B, K, G, DH)), jnp.bfloat16)
        k, k_nan = _caches(rng, (L, B, K, S, DH), pos, layer, slot_axis=2)
        v, v_nan = _caches(rng, (L, B, K, S, DH), pos, layer, slot_axis=2)
        want = grouped_decode_ref(q, k, v, pos_a, layer=layer)
        run = lambda k, v: decode_attention(  # noqa: E731
            q, k, v, pos_a, layer, mesh=mesh, scale=DH ** -0.5, block=BLK)
        got, got_nan = run(k, v), run(k_nan, v_nan)
    else:
        qc = jnp.asarray(rng.standard_normal((B, H, C + R)), jnp.bfloat16)
        lat, lat_nan = _caches(rng, (L, B, S, C + R), pos, layer, slot_axis=1)
        scale = (C + R) ** -0.5
        want = latent_decode_ref(qc, lat, pos_a, scale=scale, value_width=C,
                                 layer=layer)
        run = lambda lat: decode_attention(  # noqa: E731
            qc[:, None], lat, None, pos_a, layer, mesh=mesh, scale=scale,
            value_width=C, block=BLK)[:, 0]
        got, got_nan = run(lat), run(lat_nan)
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got_nan), np.asarray(got))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=TOL, atol=TOL)


# the serving cells' models at smoke size, in float32; mixtral with a
# ring buffer shorter than the cache's positions
STEP_CFGS = {
    "granite-8b": {},
    "mixtral-8x22b": {"window": 16},
    "moonshot-v1-16b-a3b": {},
}


@pytest.mark.parametrize("arch", sorted(STEP_CFGS))
def test_decode_step_on_the_kernel_path(monkeypatch, mesh, arch):
    """A whole decode step, its attention on the kernel (as a step
    compiled for TPUs takes it), gives the jnp path's logits and cache to
    float32 rounding (each layer's keys follow the layers before), rows at
    mixed depths, wrapped ones among them."""
    cfg = configs.get_smoke(arch).replace(dtype="float32",
                                          **STEP_CFGS[arch])
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    B, max_len = 4, 48
    rng = np.random.default_rng(3)
    cache = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype) * 0.5,
        M.init_cache(cfg, B, max_len))
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)), jnp.int32)
    pos = jnp.asarray([0, 5, 30, max_len - 1], jnp.int32)

    def step():
        return jax.jit(lambda c: M.decode_step(params, cfg, c, tokens, pos))

    want_logits, want_cache = step()(cache)
    rules = shd.make_rules(multi_pod=False)
    monkeypatch.setattr(attn, "_kernel_target", lambda: (mesh, rules))
    got_logits, got_cache = step()(cache)
    for g, w in zip(jax.tree_util.tree_leaves((got_logits, got_cache)),
                    jax.tree_util.tree_leaves((want_logits, want_cache))):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)
