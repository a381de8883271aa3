"""Per-kernel correctness: Pallas (interpret=True on CPU) vs the pure-jnp
oracle in ref.py, swept over shapes and dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rglru.ops import lru_scan
from repro.kernels.rglru.ref import lru_scan_ref
from repro.kernels.ssd.ops import ssd
from repro.kernels.ssd.ref import ssd_ref


def rngs(*shapes, dtype=jnp.float32, seed=0):
    key = jax.random.PRNGKey(seed)
    keys = jax.random.split(key, len(shapes))
    return [jax.random.normal(k, s, dtype) for k, s in zip(keys, shapes)]


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("B,Sq,Sk,H,K,D", [
    (1, 128, 128, 4, 4, 64),     # MHA square
    (2, 256, 256, 8, 2, 64),     # GQA 4:1
    (1, 128, 384, 4, 1, 128),    # MQA, Sk > Sq (decode-ish), head_dim 128
    (2, 384, 384, 6, 2, 32),     # non-pow2 head count, 3 k-blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(B, Sq, Sk, H, K, D, dtype):
    q, = rngs((B, Sq, H, D), dtype=dtype, seed=1)
    k, v = rngs((B, Sk, K, D), (B, Sk, K, D), dtype=dtype, seed=2)
    out = flash_attention(q, k, v, causal=True, use_pallas=True,
                          block_q=128, block_k=128)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


@pytest.mark.parametrize("window", [64, 128, 256])
def test_flash_attention_sliding_window(window):
    B, S, H, K, D = 1, 384, 4, 2, 64
    q, k, v = rngs((B, S, H, D), (B, S, K, D), (B, S, K, D), seed=3)
    out = flash_attention(q, k, v, causal=True, window=window,
                          use_pallas=True)
    ref = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_noncausal():
    B, S, H, K, D = 1, 256, 4, 4, 64
    q, k, v = rngs((B, S, H, D), (B, S, K, D), (B, S, K, D), seed=4)
    out = flash_attention(q, k, v, causal=False, use_pallas=True)
    ref = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_block_shape_independence():
    """Numerics must not depend on the BlockSpec tiling choice."""
    B, S, H, K, D = 1, 512, 4, 2, 64
    q, k, v = rngs((B, S, H, D), (B, S, K, D), (B, S, K, D), seed=5)
    outs = [flash_attention(q, k, v, causal=True, use_pallas=True,
                            block_q=bq, block_k=bk)
            for bq, bk in [(128, 128), (256, 128), (128, 256), (512, 512)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------------- SSD
@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 128, 4, 16, 16, 32),
    (2, 256, 8, 64, 128, 64),     # mamba2-130m-like head shape
    (1, 96, 2, 32, 32, 32),       # S not a multiple of 2*chunk
    (1, 100, 2, 16, 16, 32),      # padding path (S % chunk != 0)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_matches_ref(B, S, H, P, N, chunk, dtype):
    x, = rngs((B, S, H, P), dtype=dtype, seed=10)
    key = jax.random.PRNGKey(11)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    dt = jax.nn.softplus(jax.random.normal(k1, (B, S, H))).astype(dtype)
    A = -jnp.exp(jax.random.normal(k2, (H,)))
    Bm = jax.random.normal(k3, (B, S, N), dtype)
    Cm = jax.random.normal(k4, (B, S, N), dtype)
    out = ssd(x, dt, A, Bm, Cm, chunk=chunk, use_pallas=True)
    ref = ssd_ref(x, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               **TOL[dtype])


def test_ssd_sequential_oracle():
    """The chunked ref itself must equal a plain sequential recurrence."""
    B, S, H, P, N = 1, 64, 2, 8, 8
    x, = rngs((B, S, H, P), seed=12)
    key = jax.random.PRNGKey(13)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    dt = jax.nn.softplus(jax.random.normal(k1, (B, S, H)))
    A = -jnp.exp(jax.random.normal(k2, (H,)))
    Bm = jax.random.normal(k3, (B, S, N))
    Cm = jax.random.normal(k4, (B, S, N))

    h = np.zeros((B, H, P, N), np.float32)
    ys = []
    for t in range(S):
        a = np.exp(np.asarray(dt[:, t]) * np.asarray(A))        # (B,H)
        u = np.asarray(dt[:, t])[..., None] * np.asarray(x[:, t])
        h = a[..., None, None] * h + u[..., None] * np.asarray(Bm[:, t])[:, None, None, :]
        ys.append(np.einsum("bhpn,bn->bhp", h, np.asarray(Cm[:, t])))
    seq = np.stack(ys, axis=1)
    ref = ssd_ref(x, dt, A, Bm, Cm, chunk=16)
    np.testing.assert_allclose(np.asarray(ref), seq, rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------------- RG-LRU
@pytest.mark.parametrize("B,S,W,chunk", [
    (1, 128, 64, 32), (2, 256, 128, 128), (1, 100, 32, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lru_scan_matches_ref(B, S, W, chunk, dtype):
    key = jax.random.PRNGKey(20)
    k1, k2 = jax.random.split(key)
    a = jax.nn.sigmoid(jax.random.normal(k1, (B, S, W))).astype(dtype)
    b = jax.random.normal(k2, (B, S, W), dtype)
    out = lru_scan(a, b, chunk=chunk, use_pallas=True)
    ref = lru_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


def test_lru_scan_sequential_oracle():
    B, S, W = 1, 64, 16
    key = jax.random.PRNGKey(21)
    k1, k2 = jax.random.split(key)
    a = jax.nn.sigmoid(jax.random.normal(k1, (B, S, W)))
    b = jax.random.normal(k2, (B, S, W))
    h = np.zeros((B, W), np.float32)
    hs = []
    for t in range(S):
        h = np.asarray(a[:, t]) * h + np.asarray(b[:, t])
        hs.append(h)
    np.testing.assert_allclose(np.asarray(lru_scan_ref(a, b)),
                               np.stack(hs, 1), rtol=1e-5, atol=1e-5)


# --------------------------------------------- prefill/decode agreement
def test_ssd_prefill_decode_agree():
    """Running the chunked scan then stepping one token must equal the
    full-sequence scan — the serving path's core invariant."""
    from repro.kernels.ssd.ref import ssd_decode_step_ref
    B, S, H, P, N = 1, 65, 2, 8, 8
    x, = rngs((B, S, H, P), seed=30)
    key = jax.random.PRNGKey(31)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    dt = jax.nn.softplus(jax.random.normal(k1, (B, S, H)))
    A = -jnp.exp(jax.random.normal(k2, (H,)))
    Bm = jax.random.normal(k3, (B, S, N))
    Cm = jax.random.normal(k4, (B, S, N))
    full = ssd_ref(x, dt, A, Bm, Cm, chunk=32)
    _, state = ssd_ref(x[:, :-1], dt[:, :-1], A, Bm[:, :-1], Cm[:, :-1],
                       chunk=32, return_state=True)
    y, _ = ssd_decode_step_ref(state, x[:, -1], dt[:, -1], A, Bm[:, -1],
                               Cm[:, -1])
    np.testing.assert_allclose(np.asarray(y), np.asarray(full[:, -1]),
                               rtol=2e-4, atol=2e-4)
