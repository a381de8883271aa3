"""Flash attention as a Pallas TPU kernel.

TPU-native design (not a CUDA port): the grid is
``(batch, q_heads, q_blocks, kv_blocks)`` with the kv dimension declared
*arbitrary* (sequential) so the online-softmax running state — max ``m``,
normaliser ``l`` and the output accumulator — lives in VMEM scratch and is
carried across kv steps. Q/K/V tiles stream HBM→VMEM per BlockSpec; tile
sizes default to 128 (MXU-aligned: the (block_q × head_dim) @ (head_dim ×
block_k) products hit the 128×128 systolic array shape). GQA is handled in
the K/V index maps (q head h reads kv head h // group), so kv tiles are
fetched once per group without materialising repeated heads in HBM.

Softmax statistics are computed in float32 regardless of input dtype
(bf16-safe). Fully masked tiles are cheap: masking is applied in-register
before the row-max update, so they contribute nothing to l/acc.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

__all__ = ["flash_attention_kernel"]


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int | None,
            block_q: int, block_k: int, kv_len: int, num_kv_blocks: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)            # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < kv_len                            # seq padding
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= qpos - kpos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                             # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == num_kv_blocks - 1)
    def _finish():
        l = l_scr[...]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0, :, :] = (acc_scr[...] / safe).astype(o_ref.dtype)


def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int | None = None,
                           scale: float | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = True) -> jax.Array:
    """q: (B, H, Sq, D); k, v: (B, K, Sk, D). Returns (B, H, Sq, D)."""
    B, H, Sq, D = q.shape
    _, K, Sk, _ = k.shape
    assert H % K == 0, (H, K)
    group = H // K
    scale = D ** -0.5 if scale is None else scale

    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(Sk, 8))
    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq = q.shape[2] // block_q
    nk = k.shape[2] // block_k

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          block_q=block_q, block_k=block_k, kv_len=Sk,
                          num_kv_blocks=nk),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # normaliser l
            pltpu.VMEM((block_q, D), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    if pq:
        out = out[:, :, :Sq, :]
    return out
