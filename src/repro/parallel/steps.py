"""Distributed step functions: train_step / prefill_step / serve_step, and
the row patch that feeds the serve step's device-resident inputs.

Each maker binds (config, mesh, rules) and returns a jitted function with
explicit in/out shardings (pjit). The trainer, the serving engine and the
smoke tests execute them; ``tests/test_tpu_compile.py`` lowers them against
ShapeDtypeStruct inputs for a described TPU.

Distributed-optimization features:
  * microbatch gradient accumulation (``lax.scan`` over the leading
    microbatch dim — keeps peak activation memory at 1/M),
  * donated state/cache buffers, updated in place: the decode's layer scan
    carries the stacked cache and writes only each row's new key and value
    (``models.model.decode_step``), so no step copies the cache,
  * activation sharding constraints via repro.parallel.ctx,
  * rematerialised layer stacks (``models.model`` checkpoints each layer
    body) — compute/comm overlap then falls out of XLA's latency-hiding
    scheduler on real hardware.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import model as M
from repro.parallel import sharding as shd
from repro.parallel.ctx import fit_pspec, sharding_ctx
from repro.train.optimizer import OptConfig, adamw_update, init_opt

__all__ = ["make_train_step", "make_serve_step", "make_prefill_step",
           "make_row_patch", "row_shardings",
           "train_state_shardings", "abstract_train_state",
           "batch_shardings", "abstract_batch"]


# ----------------------------------------------------------------- state
def abstract_train_state(cfg, dtype=jnp.float32):
    params = M.abstract_params(cfg, dtype)
    like = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), t)
    return {"params": params, "opt": {"mu": like(params), "nu": like(params)},
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


def init_train_state(cfg, rng, dtype=jnp.float32):
    params = M.init_params(cfg, rng, dtype)
    return {"params": params, "opt": init_opt(params),
            "step": jnp.int32(0)}


def train_state_shardings(cfg, mesh, rules):
    specs = M.param_shapes(cfg)
    pshard = shd.param_shardings(specs, rules, mesh)
    return {"params": pshard, "opt": {"mu": pshard, "nu": pshard},
            "step": NamedSharding(mesh, P())}


# ----------------------------------------------------------------- batches
def abstract_batch(cfg, global_batch: int, seq_len: int):
    """ShapeDtypeStructs for one training/prefill batch."""
    dt = M.compute_dtype(cfg)
    F = cfg.frontend_tokens
    text = seq_len - F if cfg.family == "vlm" else seq_len
    b = {"tokens": jax.ShapeDtypeStruct((global_batch, text), jnp.int32)}
    if cfg.family == "vlm":
        b["vision_embeds"] = jax.ShapeDtypeStruct((global_batch, F, cfg.d_model), dt)
    if cfg.family == "audio":
        b["audio_embeds"] = jax.ShapeDtypeStruct((global_batch, F, cfg.d_model), dt)
    return b


def batch_shardings(cfg, mesh, rules, *, microbatches: int = 1):
    bp = shd.batch_pspec(rules)
    dp = tuple(bp)[0]

    def spec(ndim):
        if microbatches > 1:
            return NamedSharding(mesh, P(None, dp, *([None] * (ndim - 2))))
        return NamedSharding(mesh, P(dp, *([None] * (ndim - 1))))

    out = {"tokens": spec(2 + (1 if microbatches > 1 else 0))}
    if cfg.family == "vlm":
        out["vision_embeds"] = spec(3 + (1 if microbatches > 1 else 0))
    if cfg.family == "audio":
        out["audio_embeds"] = spec(3 + (1 if microbatches > 1 else 0))
    return out


# -------------------------------------------------------------- train step
def make_train_step(cfg, mesh, rules, *, opt: OptConfig | None = None,
                    microbatches: int = 1):
    opt = opt or OptConfig()
    state_sh = train_state_shardings(cfg, mesh, rules)
    batch_sh = batch_shardings(cfg, mesh, rules, microbatches=microbatches)
    metric_sh = NamedSharding(mesh, P())

    def loss_of(params, batch):
        with sharding_ctx(mesh, rules):
            return M.loss_fn(params, cfg, batch)

    def train_step(state, batch):
        params = state["params"]
        if microbatches == 1:
            loss, grads = jax.value_and_grad(loss_of)(params, batch)
        else:
            def acc_body(carry, mb):
                loss_acc, grads_acc = carry
                l, g = jax.value_and_grad(loss_of)(params, mb)
                return (loss_acc + l,
                        jax.tree_util.tree_map(jnp.add, grads_acc, g)), None

            zero_g = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(acc_body, (jnp.float32(0.0), zero_g),
                                            batch)
            inv = 1.0 / microbatches
            loss = loss * inv
            grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
        new_params, new_opt, om = adamw_update(grads, state["opt"], params,
                                               opt, state["step"])
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        metrics = {"loss": loss, **om}
        return new_state, metrics

    return jax.jit(train_step,
                   in_shardings=(state_sh, batch_sh),
                   out_shardings=(state_sh, {"loss": metric_sh,
                                             "grad_norm": metric_sh,
                                             "lr": metric_sh}),
                   donate_argnums=(0,))


# -------------------------------------------------------------- serve steps
def row_shardings(mesh, rules, global_batch: int):
    """Shardings of the per-row tokens (B, 1) and positions (B,) that the
    serve, prefill and patch steps take and return."""
    dp = tuple(shd.batch_pspec(rules))[0]
    B = global_batch
    return (NamedSharding(mesh, fit_pspec(P(dp, None), (B, 1), mesh)),
            NamedSharding(mesh, fit_pspec(P(dp), (B,), mesh)))


def make_serve_step(cfg, mesh, rules, *, global_batch: int, max_len: int):
    """One greedy decode step over a persistent sharded cache (donated).

    ``serve_step(params, cache, tokens (B, 1), pos (B,))`` returns the
    argmax tokens (B, 1) int32, the next positions (B,) and the cache, so
    each step's outputs can be the next step's inputs without a host read.
    A row at position 0 is idle (a request's first decode is at its
    prompt's length, at least 1): it stays there and decodes token 0, so
    the idle rows of a mixture of experts all take one route.
    """
    cache_sh = jax.tree_util.tree_map(
        lambda p: NamedSharding(mesh, p),
        shd.cache_pspecs(M.cache_shapes(cfg, global_batch, max_len), rules,
                         mesh, cfg))
    specs = M.param_shapes(cfg)
    param_sh = shd.param_shardings(specs, rules, mesh)
    tok_sh, pos_sh = row_shardings(mesh, rules, global_batch)

    def serve_step(params, cache, tokens, pos):
        with sharding_ctx(mesh, rules):
            logits, new_cache = M.decode_step(params, cfg, cache, tokens, pos)
        live = pos > 0
        nxt = jnp.where(live, jnp.argmax(logits, axis=-1), 0)
        return (nxt.astype(jnp.int32)[:, None], jnp.where(live, pos + 1, 0),
                new_cache)

    return jax.jit(serve_step,
                   in_shardings=(param_sh, cache_sh, tok_sh, pos_sh),
                   out_shardings=(tok_sh, pos_sh, cache_sh),
                   donate_argnums=(1,))


def make_row_patch(mesh, rules, *, global_batch: int):
    """``row_patch(tokens, pos, row, tok (1, 1), start)`` writes one row's
    token and position into the decode's inputs. The positions are donated;
    the tokens are not, since they are a decode's output that the host has
    yet to read."""
    def row_patch(tokens, pos, row, tok, start):
        return tokens.at[row].set(tok[0]), pos.at[row].set(start)

    return jax.jit(row_patch,
                   out_shardings=row_shardings(mesh, rules, global_batch),
                   donate_argnums=(1,))


def make_prefill_step(cfg, mesh, rules, *, global_batch: int, seq_len: int,
                      max_len: int):
    """``prefill_step(params, batch)`` returns the argmax token after each
    prompt, (B, 1) int32, and the decode cache of the prompts."""
    cache_sh = jax.tree_util.tree_map(
        lambda p: NamedSharding(mesh, p),
        shd.cache_pspecs(M.cache_shapes(cfg, global_batch, max_len), rules,
                         mesh, cfg))
    specs = M.param_shapes(cfg)
    param_sh = shd.param_shardings(specs, rules, mesh)
    batch_sh = batch_shardings(cfg, mesh, rules)
    tok_sh, _ = row_shardings(mesh, rules, global_batch)

    def prefill_step(params, batch):
        with sharding_ctx(mesh, rules):
            logits, cache = M.prefill(params, cfg, batch, max_len)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None], cache

    return jax.jit(prefill_step,
                   in_shardings=(param_sh, batch_sh),
                   out_shardings=(tok_sh, cache_sh))
