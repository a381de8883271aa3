"""Public wrapper for ragged decode attention: live lengths from positions,
and the kernel run per shard of the cache on a mesh of several devices.

On a mesh the kernel runs inside ``shard_map`` over the cache's own
partitioning, so XLA never gathers the cache for it: rows split over the
data axes and KV heads over the model axis are independent. Where the
cache's slots are split instead (a latent cache, or fewer KV heads than
the model axis), each shard attends over its own slots and the shards'
results are merged by their softmax sums (the log-sum-exp the kernel
returns).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.decode_attention.kernel import decode_attention_kernel

__all__ = ["decode_attention"]


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array | None,
                     pos: jax.Array, layer, *, mesh, scale: float,
                     value_width: int | None = None, cache_spec: P = P(),
                     block: int | None = None) -> jax.Array:
    """Attention of each row's query token over the live slots of its
    cache, ``min(pos + 1, S)`` of them.

    q: (B, K, G, D); k, v: (B, K, S, D), or (L, B, K, S, D) with ``layer``
    this layer's index; or the latent layout, k (B, S, D) or (L, B, S, D)
    and ``v`` None, the rows being the values too, with K = 1. pos: (B,)
    int32. ``cache_spec`` is the stacked cache's PartitionSpec on
    ``mesh``; the kernel is compiled for ``mesh``'s platform and
    interpreted off TPUs. Returns (B, K, G, value_width) float32.
    """
    if layer is None:                       # one layer's cache: a stack of 1
        k = k[None]
        v = None if v is None else v[None]
        layer = 0
    layer = jnp.asarray(layer, jnp.int32)
    lengths = jnp.minimum(pos.astype(jnp.int32) + 1, k.shape[-2])
    caches = (k,) if v is None else (k, v)
    entries = tuple(cache_spec) + (None,) * (k.ndim - len(cache_spec))
    if v is not None:                       # (L, B, K, S, D)
        _, rows, heads, slots, width = entries
    else:                                   # (L, B, S, D)
        (_, rows, slots, width), heads = entries, None
    assert entries[0] is None and width is None, cache_spec
    if mesh.size == 1:
        slots = None
    run = functools.partial(
        decode_attention_kernel, scale=scale, value_width=value_width,
        block=block, interpret=mesh.devices.flat[0].platform != "tpu")

    def attend(q, lengths, layer, k, v=None):
        """The kernel over one shard's slots, merged across the shards."""
        S_loc = k.shape[-2]
        if slots is not None:
            lengths = jnp.clip(lengths - jax.lax.axis_index(slots) * S_loc,
                               0, S_loc)
        # the latent goes in slots-minor, as the TPU lays it out
        o, lse = run(q, k if v is not None else jnp.swapaxes(k, -1, -2), v,
                     lengths, layer)
        if slots is None:
            return o
        w = jnp.exp(lse - jax.lax.pmax(lse, slots))
        return jax.lax.psum(o * w, slots) / jax.lax.psum(w, slots)

    if mesh.size == 1:
        return attend(q, lengths, layer, *caches)
    return jax.shard_map(
        attend, mesh=mesh,
        in_specs=(P(rows, heads), P(rows), P(), *(cache_spec,) * len(caches)),
        out_specs=P(rows, heads), check_vma=False)(q, lengths, layer, *caches)
