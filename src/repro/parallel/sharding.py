"""Logical-axis sharding rules: ParamSpec.axes → PartitionSpec.

Every parameter/cache/activation dimension carries a *logical* axis name;
a rule set maps logical names to mesh axes. :func:`make_rules` builds one:

``baseline``   plain DP × TP: batch over (pod, data); vocab/heads/ff/experts
               over model; parameters replicated across the data axis (the
               classic megatron-style layout).
``fsdp``       additionally shards every parameter's `embed` dim over
               (pod, data) — fully-sharded data parallel — so params and
               optimizer state scale with the whole mesh.
``tp2d``       serving: parameters sharded 2-D over (data × model) on the
               ff dim and resident, with no batch sharding.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.layers import ParamSpec, stacked
from repro.parallel.ctx import axes_to_pspec, fit_pspec

__all__ = ["make_rules", "spec_to_pspec", "param_shardings", "batch_pspec",
           "cache_pspecs", "cache_leaf_pspec"]


def make_rules(*, multi_pod: bool, fsdp: bool = False,
               tp2d: bool = False) -> dict:
    dp = ("pod", "data") if multi_pod else ("data",)
    if tp2d:
        # Serving rules: parameters sharded 2-D over (data × model) on the
        # ff dim, everything resident — NO per-step FSDP all-gather (which
        # at decode batch=1 costs ~GBs of wire per layer for zero reuse).
        # The per-layer collective is one small activation all-reduce.
        return {
            "batch": (), "embed": (),
            "vocab": ("model",), "heads": ("model",), "kv_heads": ("model",),
            "ff": dp + ("model",), "experts": (),
            "head": (), "layers": (), "seq": (),
            "act_embed": (), "cap": (), None: (),
        }
    return {
        "batch": dp,
        "vocab": ("model",),
        "heads": ("model",),
        "kv_heads": ("model",),
        "ff": ("model",),
        "experts": ("model",),
        "embed": dp if fsdp else (),
        "head": (),
        "layers": (),
        "seq": (),
        "act_embed": (),                  # activation d_model dim
        "cap": (),                        # MoE capacity dim
        None: (),
    }


def spec_to_pspec(spec: ParamSpec, rules: dict, mesh: Mesh) -> P:
    """PartitionSpec for one ParamSpec on ``mesh``, without the mesh axes a
    dim does not divide by (e.g. 10 heads on a 16-way model axis →
    replicate rather than fail)."""
    return fit_pspec(axes_to_pspec(spec.axes, rules), spec.shape, mesh)


def param_shardings(specs, rules: dict, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, spec_to_pspec(s, rules, mesh)), specs,
        is_leaf=lambda x: isinstance(x, ParamSpec))


def batch_pspec(rules: dict) -> P:
    dp = tuple(rules["batch"])
    return P(dp if len(dp) > 1 else (dp[0] if dp else None))


def cache_leaf_pspec(shape, *, bdim: int, head_major: bool, rules: dict,
                     mesh: Mesh, cfg) -> P:
    """PartitionSpec for one decode-cache leaf: batch dim ``bdim`` over DP
    axes, kv-head / state dims over model where divisible. Attention k/v
    are ``head_major`` (B, K, slots, Dh); the other leaves put a
    sequence-like dim first (B, F|W-1, ...)."""
    dp = tuple(rules["batch"])
    dp_entry = dp if len(dp) > 1 else (dp[0] if dp else None)
    entries = [None] * len(shape)
    dp_n = 1
    for a in dp:
        dp_n *= mesh.shape[a]
    if shape[bdim] % dp_n == 0:
        entries[bdim] = dp_entry
    # shard kv-heads/state heads over model when divisible…
    model_n = mesh.shape["model"]
    placed = False
    for i in range(bdim + (1 if head_major else 2), len(shape)):
        if shape[i] in (cfg.num_kv_heads, cfg.ssm_heads) and \
                shape[i] % model_n == 0:
            entries[i] = "model"
            placed = True
            break
    # …else shard the sequence-slots dim (GQA kv < model axis: the
    # standard sequence-sharded KV cache — keeps a 32k×128-row cache
    # at ~2.5 GB/chip instead of 40 GB/chip)
    if not placed and len(shape) >= bdim + 3:
        slots_dim = bdim + (2 if head_major else 1)
        if shape[slots_dim] % model_n == 0:
            entries[slots_dim] = "model"
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def cache_pspecs(cache_shape_tree, rules: dict, mesh: Mesh, cfg):
    """PartitionSpecs for a decode cache (:func:`cache_leaf_pspec` per
    leaf; layer-stacked leaves are (L, B, ...), unstacked (B, ...))."""
    return jax.tree_util.tree_map_with_path(
        lambda path, sd: cache_leaf_pspec(
            sd[0], bdim=1 if stacked(path) else 0,
            head_major=path[-1].key in ("k", "v"), rules=rules, mesh=mesh,
            cfg=cfg),
        cache_shape_tree,
        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))
