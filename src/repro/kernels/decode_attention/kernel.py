"""Single-token attention over a layer-stacked cache, reading only each
row's live positions, as a Pallas TPU kernel.

The grid is ``(rows, position blocks)``, the block axis sequential
("arbitrary"), so the online-softmax state (running max ``m``, sum ``l``
and the float32 accumulator) lives in VMEM scratch across a row's blocks.
A block holds all of a row's KV heads. Each row ``b`` has ``n_b`` live
positions, slots ``[0, n_b)`` (a ring buffer that has wrapped has every
slot live); a block at or past the row's last live block maps, in the
index maps, to that last block, so the pipeline issues no DMA for it, and
``pl.when`` skips its compute. A decode therefore reads ``ceil(n_b /
block)`` blocks of a row, not the whole cache.

Two cache layouts, told apart by the inputs' shapes:

* grouped: keys and values are separate caches, ``(L, B, K, S, D)``, and
  the query is ``(B, K, G, D)``: ``G`` query heads per KV head;
* latent: one cache whose rows are both the keys and the values, read
  once for both, with the query ``(B, 1, H, D)``: all heads against the
  one shared row. It comes slots-minor, ``(L, B, D, S)``: the TPU lays a
  576-wide latent cache out with its slots minor (no lane padding), so
  this view of it is the cache's own bytes, passed with no copy.

The layer and the live lengths arrive as scalar prefetch, so a layer scan
hands the whole stacked cache to the kernel and no layer is sliced out
(a sliced operand of a custom call is materialised). The dot operands are
the cache's dtype, with float32 sums. Slots past ``n_b`` in the last block
are masked out of both the scores and the values, so what they hold never
reaches the result.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

__all__ = ["decode_attention_kernel", "block_size"]


def block_size(slots: int, row_bytes: int, minimum: int,
               target_bytes: int = 1 << 20) -> int:
    """Positions per block: the largest power of two from ``minimum`` up
    that divides ``slots`` and holds at most ``target_bytes`` of cache
    rows; all ``slots`` where ``minimum`` does not divide them."""
    if slots % minimum:
        return slots
    blk = minimum
    while blk * 2 * row_bytes <= target_bytes and slots % (blk * 2) == 0:
        blk *= 2
    return blk


def _kernel(layer_ref, len_ref, q_ref, k_ref, *rest, scale: float, block: int,
            heads: int, grouped: bool, value_width: int):
    if grouped:
        v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
        key_dims, value_dims = ((1,), (1,)), ((1,), (0,))
    else:                                   # the key rows are the values
        v_ref = k_ref
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
        key_dims, value_dims = ((1,), (0,)), ((1,), (1,))
    del layer_ref
    j = pl.program_id(1)
    n = len_ref[pl.program_id(0)]
    last = jnp.maximum((n + block - 1) // block, 1) - 1

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j <= last)
    def _attend():
        at = j * block
        live = at + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1) < n
        # the value rows' live mask: slots down a block, or across one
        live_v = (at + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0) < n
                  if grouped else live)
        for h in range(heads):
            q = q_ref[h]                              # (G, D)
            k = k_ref[h] if grouped else k_ref[...]   # (block, D) | (D, block)
            v = v_ref[h] if grouped else k
            s = jax.lax.dot_general(q, k, (key_dims, ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(live, s * scale, NEG_INF)   # (G, block)
            m_prev = m_scr[h]                         # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(live, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=-1, keepdims=True)
            v = jnp.where(live_v, v, jnp.zeros_like(v))
            acc_scr[h] = alpha * acc_scr[h] + jax.lax.dot_general(
                p.astype(v.dtype), v, (value_dims, ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    @pl.when(j == last)
    def _finish():
        for h in range(heads):
            l = l_scr[h]
            o_ref[h] = (acc_scr[h] / jnp.where(l > 0.0, l, 1.0)
                        )[:, :value_width].astype(o_ref.dtype)
            lse_ref[h] = jnp.where(l > 0.0, m_scr[h] + jnp.log(l), NEG_INF)


def decode_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array | None,
                            lengths: jax.Array, layer: jax.Array, *,
                            scale: float, value_width: int | None = None,
                            block: int | None = None,
                            interpret: bool = False):
    """Attention of one query token a row over the first ``lengths[b]``
    slots of layer ``layer`` of a stacked cache.

    q: (B, K, G, D); k: (L, B, K, S, D) with ``v`` the same shape, or the
    latent layout: k (L, B, D, S), ``v`` None and K = 1. lengths: (B,)
    int32 in [0, S]; layer: int32 scalar. Returns (o (B, K, G,
    value_width) float32, the first ``value_width`` columns of the
    weighted sum, default D; lse (B, K, G, 1) float32, the log of each
    softmax's sum, ``NEG_INF`` for a row with no live slot, whose ``o``
    is 0).
    """
    B, K, G, D = q.shape
    grouped = v is not None
    S = k.shape[-2] if grouped else k.shape[-1]
    value_width = D if value_width is None else value_width
    if block is None:      # slots down the sublanes, or across the lanes
        block = (block_size(S, K * D * k.dtype.itemsize, 16) if grouped
                 else block_size(S, D * k.dtype.itemsize, 128))
    assert S % block == 0, (S, block)

    def kv_map(b, j, layer_ref, len_ref):
        last = jnp.maximum((len_ref[b] + block - 1) // block, 1) - 1
        at = jnp.minimum(j, last)
        if grouped:
            return layer_ref[0], b, 0, at, 0
        return layer_ref[0], b, 0, at

    kv_spec = pl.BlockSpec((None, None, K, block, D) if grouped
                           else (None, None, D, block), kv_map)
    row = lambda b, j, layer_ref, len_ref: (b, 0, 0, 0)  # noqa: E731
    in_specs = [pl.BlockSpec((None, K, G, D), row), kv_spec]
    operands = [q, k]
    if grouped:
        in_specs.append(kv_spec)
        operands.append(v)

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, block=block, heads=K,
                          grouped=grouped, value_width=value_width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, S // block),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((None, K, G, value_width), row),
                       pl.BlockSpec((None, K, G, 1), row)],
            scratch_shapes=[pltpu.VMEM((K, G, 1), jnp.float32),
                            pltpu.VMEM((K, G, 1), jnp.float32),
                            pltpu.VMEM((K, G, D), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, K, G, value_width), jnp.float32),
                   jax.ShapeDtypeStruct((B, K, G, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lengths.astype(jnp.int32),
      *operands)
