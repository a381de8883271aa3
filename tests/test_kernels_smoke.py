"""Data-plane smoke: one tiny shape per Pallas kernel, interpret mode.

The CI-sized cousin of test_kernels.py: a single minimal parametrisation
per kernel — enough to catch an import error, an API drift in the Pallas
toolchain or a gross numerical break, in seconds instead of the full
grid's minutes.
The exhaustive shape/dtype sweep stays out of the CI gate.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rglru.ops import lru_scan
from repro.kernels.rglru.ref import lru_scan_ref
from repro.kernels.ssd.ops import ssd
from repro.kernels.ssd.ref import ssd_ref


def _rngs(*shapes, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return [jax.random.normal(k, s, jnp.float32) for k, s in zip(keys, shapes)]


def test_flash_attention_smoke():
    B, S, H, K, D = 1, 128, 2, 2, 64
    q, k, v = _rngs((B, S, H, D), (B, S, K, D), (B, S, K, D), seed=1)
    out = flash_attention(q, k, v, causal=True, use_pallas=True,
                          block_q=128, block_k=128)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ssd_smoke():
    B, S, H, P, N, chunk = 1, 128, 2, 16, 16, 32
    x, = _rngs((B, S, H, P), seed=10)
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(11), 4)
    dt = jax.nn.softplus(jax.random.normal(k1, (B, S, H)))
    A = -jnp.exp(jax.random.normal(k2, (H,)))
    Bm = jax.random.normal(k3, (B, S, N), jnp.float32)
    Cm = jax.random.normal(k4, (B, S, N), jnp.float32)
    out = ssd(x, dt, A, Bm, Cm, chunk=chunk, use_pallas=True)
    ref = ssd_ref(x, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_lru_scan_smoke():
    B, S, W, chunk = 1, 128, 64, 64
    a_raw, b = _rngs((B, S, W), (B, S, W), seed=20)
    a = jax.nn.sigmoid(a_raw)   # stable decay in (0, 1)
    out = lru_scan(a, b, chunk=chunk, use_pallas=True)
    ref = lru_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
