"""AdamW with global-norm clipping, as pure pytree functions.

Optimizer state mirrors the param tree (mu, nu), so the same sharding tree
applies — under FSDP rules the optimizer state is fully sharded too. The
moments are float32. No external dependency (optax is not
in the image); the update is the textbook decoupled-weight-decay Adam.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

__all__ = ["OptConfig", "init_opt", "adamw_update", "global_norm"]


@dataclass(frozen=True)
class OptConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100


def init_opt(params):
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)  # noqa: E731
    return {"mu": jax.tree_util.tree_map(zeros, params),
            "nu": jax.tree_util.tree_map(zeros, params)}


def global_norm(tree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in leaves))


def _schedule(oc: OptConfig, step: jax.Array) -> jax.Array:
    warm = jnp.minimum(1.0, (step + 1) / max(oc.warmup_steps, 1))
    return oc.lr * warm


def adamw_update(grads, opt_state, params, oc: OptConfig, step: jax.Array):
    """Returns (new_params, new_opt_state, metrics)."""
    gn = global_norm(grads)
    scale = jnp.minimum(1.0, oc.clip_norm / jnp.maximum(gn, 1e-9))
    lr = _schedule(oc, step)
    t = (step + 1).astype(jnp.float32)
    c1 = 1.0 - oc.b1 ** t
    c2 = 1.0 - oc.b2 ** t

    def upd(p, g, mu, nu):
        g = g.astype(jnp.float32) * scale
        mu = oc.b1 * mu + (1 - oc.b1) * g
        nu = oc.b2 * nu + (1 - oc.b2) * jnp.square(g)
        step_dir = (mu / c1) / (jnp.sqrt(nu / c2) + oc.eps)
        newp = p.astype(jnp.float32) - lr * (step_dir + oc.weight_decay
                                             * p.astype(jnp.float32))
        return newp.astype(p.dtype), mu, nu

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_mu = treedef.flatten_up_to(opt_state["mu"])
    flat_nu = treedef.flatten_up_to(opt_state["nu"])
    out = [upd(p, g, m, n) for p, g, m, n in
           zip(flat_p, flat_g, flat_mu, flat_nu)]
    new_p = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
    new_mu = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
    new_nu = jax.tree_util.tree_unflatten(treedef, [o[2] for o in out])
    return new_p, {"mu": new_mu, "nu": new_nu}, {"grad_norm": gn, "lr": lr}
