"""The jnp path of decode attention: the oracle the kernel is tested
against, and the path of every step compiled for devices other than TPUs.

Both read a layer's whole cache and mask each slot by its age: slot ``j``
of a row holds the token ``(pos % S - j) mod S`` steps in the past, and is
live where that age is at most ``min(pos, S - 1)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["grouped_decode_ref", "latent_decode_ref"]

NEG_INF = -1e30


def _valid(pos: jax.Array, Smax: int) -> jax.Array:
    slot = pos % Smax
    age = (slot[:, None] - jnp.arange(Smax)[None, :]) % Smax   # 0 = now
    return age, age <= jnp.minimum(pos, Smax - 1)[:, None]      # written yet?


def grouped_decode_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                       pos: jax.Array, *, layer=None,
                       window: int | None = None) -> jax.Array:
    """q: (B, K, G, Dh); k, v: (B, K, S, Dh), or (L, B, K, S, Dh) with
    ``layer``; pos: (B,) int32. Scores and sums in float32. Returns
    (B, K, G, Dh) float32."""
    Dh, Smax = q.shape[-1], k.shape[-2]
    layer_k = k if layer is None else k[layer]
    layer_v = v if layer is None else v[layer]
    qf = q.astype(jnp.float32)
    kf = layer_k.astype(jnp.float32)
    vf = layer_v.astype(jnp.float32)
    s = jnp.einsum("bkgd,bktd->bkgt", qf, kf) * (Dh ** -0.5)
    age, valid = _valid(pos, Smax)
    if window is not None:
        valid &= age < window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    pattn = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgt,bktd->bkgd", pattn, vf)


def latent_decode_ref(qc: jax.Array, cache: jax.Array, pos: jax.Array, *,
                      scale: float, value_width: int,
                      layer=None) -> jax.Array:
    """Absorbed latent attention: every head's query ``qc`` (B, H, W)
    against the latent rows ``cache`` (B, S, W), or (L, B, S, W) with
    ``layer``, which are both the keys and the values. The dots take the
    cache's dtype with float32 sums. Returns the first ``value_width``
    columns of the weighted sum, (B, H, value_width) float32."""
    lat = cache if layer is None else cache[layer]
    s = jnp.einsum("bhc,btc->bht", qc, lat,
                   preferred_element_type=jnp.float32) * scale
    _, valid = _valid(pos, lat.shape[-2])
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    pattn = jax.nn.softmax(s, axis=-1).astype(lat.dtype)
    # the whole row, rope part included, so no narrower slice of the cache
    # is copied; the columns past value_width are dropped after
    return jnp.einsum("bht,btc->bhc", pattn, lat,
                      preferred_element_type=jnp.float32)[..., :value_width]
