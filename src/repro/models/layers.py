"""Parameter metadata system + common layers.

Models are *metadata first*: every architecture defines its parameter tree
as a nested dict of :class:`ParamSpec` (shape, logical axes, init). From
that single source we derive
  - concrete initialisation (smoke tests, the e2e trainer),
  - abstract ``ShapeDtypeStruct`` trees (compiling for a described chip
    allocates nothing),
  - sharding trees (logical axes → mesh axes via `repro.parallel.sharding`).

Forward code is pure-functional JAX over the params dict. No framework
dependency beyond jax itself.
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["ParamSpec", "init_tree", "abstract_tree", "rms_norm",
           "rotary_embedding", "apply_rope", "swiglu", "geglu",
           "take_embedding", "stacked"]


class ParamSpec(NamedTuple):
    shape: tuple[int, ...]
    axes: tuple[Any, ...]          # logical axis name (or None) per dim
    init: str = "linear"           # linear | embed | zeros | ones
    fan_in_axes: tuple[int, ...] = ()   # dims contracted by the consumer
    dtype: Any = jnp.float32

    def with_prefix(self, n: int, axis_name: str = "layers") -> "ParamSpec":
        """Stack for scan-over-layers: prepend a leading layer dim."""
        return self._replace(shape=(n, *self.shape), axes=(axis_name, *self.axes))


def _fan_in(spec: ParamSpec) -> int:
    if spec.fan_in_axes:
        return max(1, math.prod(spec.shape[a] for a in spec.fan_in_axes))
    return max(1, spec.shape[0] if spec.shape else 1)


def _materialize(key, spec: ParamSpec, dtype) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "embed":
        scale = 0.02
    else:
        scale = _fan_in(spec) ** -0.5
    return (jax.random.normal(key, spec.shape, jnp.float32) * scale).astype(dtype)


def init_tree(specs, rng, dtype=jnp.float32):
    """Materialise a nested ParamSpec dict into arrays."""
    leaves, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(rng, len(leaves))
    arrays = [_materialize(k, s, dtype) for k, s in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, arrays)


def abstract_tree(specs, dtype=jnp.float32):
    """ShapeDtypeStruct stand-ins (no allocation) for ahead-of-time
    compiles."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))


# --------------------------------------------------------------------------
# layers
# --------------------------------------------------------------------------
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dt) * scale.astype(dt)


def rotary_embedding(positions: jax.Array, head_dim: int,
                     theta: float = 1e4) -> tuple[jax.Array, jax.Array]:
    """(sin, cos) tables for the given positions; shape (..., head_dim/2)."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(angles), jnp.cos(angles)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x: (..., S, H, D); sin/cos: (..., S, D/2) broadcast over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    sin = sin[..., None, :]
    cos = cos[..., None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(x_gate: jax.Array, x_up: jax.Array) -> jax.Array:
    return jax.nn.silu(x_gate) * x_up


def geglu(x_gate: jax.Array, x_up: jax.Array) -> jax.Array:
    return jax.nn.gelu(x_gate) * x_up


def take_embedding(table: jax.Array, ids: jax.Array, compute_dtype) -> jax.Array:
    return jnp.take(table, ids, axis=0).astype(compute_dtype)


def stacked(path) -> bool:
    """Is the decode-cache leaf at ``path`` stacked over its group's
    layers? A stacked leaf sits directly in its group; an unrolled group
    holds one dict per layer."""
    return len(path) == 2
