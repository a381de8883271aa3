"""Logical-axis sharding rules: ParamSpec.axes → PartitionSpec.

Every parameter/cache/activation dimension carries a *logical* axis name;
a rule set maps logical names to mesh axes. Two built-in rule sets:

``baseline``   plain DP × TP: batch over (pod, data); vocab/heads/ff/experts
               over model; parameters replicated across the data axis (the
               classic megatron-style layout).
``fsdp``       beyond-baseline: additionally shards every parameter's
               `embed` dim over (pod, data) — fully-sharded data parallel —
               so params+optimizer state scale with the whole mesh. This is
               the optimized configuration measured in EXPERIMENTS.md §Perf.

Rules are plain dicts so experiments can derive variants (the hillclimb
edits one entry at a time and re-lowers).
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.layers import ParamSpec, stacked

__all__ = ["RULES", "make_rules", "spec_to_pspec", "param_shardings",
           "tree_pspecs", "batch_pspec", "cache_pspecs", "cache_leaf_pspec",
           "constrain"]


def make_rules(*, multi_pod: bool, fsdp: bool = False,
               seq_shard: bool = False, zero: bool = False,
               tp2d: bool = False) -> dict:
    dp = ("pod", "data") if multi_pod else ("data",)
    if zero:
        # Pure ZeRO-3 data parallel over the WHOLE mesh: batch and every
        # parameter's embed dim shard over (pod, data, model); no tensor
        # parallelism. Beats DP×TP when a head/ff/expert count does not
        # divide the model axis (e.g. qwen's 40 heads on a 16-way axis
        # would replicate all attention compute 16×). §Perf hillclimb.
        dpz = dp + ("model",)
        return {
            "batch": dpz, "embed": dpz,
            "vocab": (), "heads": (), "kv_heads": (), "ff": (),
            "experts": (), "head": (), "layers": (), "seq": (),
            "act_embed": (), "cap": (), None: (),
        }
    if tp2d:
        # Serving rules: parameters sharded 2-D over (data × model) on the
        # ff dim, everything resident — NO per-step FSDP all-gather (which
        # at decode batch=1 costs ~GBs of wire per layer for zero reuse).
        # The per-layer collective is one small activation all-reduce.
        # §Perf hillclimb (mixtral long_500k).
        return {
            "batch": (), "embed": (),
            "vocab": ("model",), "heads": ("model",), "kv_heads": ("model",),
            "ff": dp + ("model",), "experts": (),
            "head": (), "layers": (), "seq": (),
            "act_embed": (), "cap": (), None: (),
        }
    rules = {
        "batch": dp,
        "vocab": ("model",),
        "heads": ("model",),
        "kv_heads": ("model",),
        "ff": ("model",),
        "experts": ("model",),
        "embed": dp if fsdp else (),
        "head": (),
        "layers": (),
        "seq": dp if seq_shard else (),   # sequence parallelism (long prefill)
        "act_embed": (),                  # activation d_model dim
        "cap": (),                        # MoE capacity dim
        None: (),
    }
    return rules


RULES = {
    "baseline": make_rules(multi_pod=False),
    "baseline_mp": make_rules(multi_pod=True),
    "fsdp": make_rules(multi_pod=False, fsdp=True),
    "fsdp_mp": make_rules(multi_pod=True, fsdp=True),
    "zero": make_rules(multi_pod=False, zero=True),
    "zero_mp": make_rules(multi_pod=True, zero=True),
    "tp2d": make_rules(multi_pod=False, tp2d=True),
    "tp2d_mp": make_rules(multi_pod=True, tp2d=True),
}


def _axes_to_pspec(axes, rules: dict, shape=None) -> P:
    out = []
    used: set[str] = set()   # a mesh axis may appear in at most one dim
    for i, ax in enumerate(axes):
        mesh_axes = rules.get(ax, ())
        if mesh_axes is None:
            mesh_axes = ()
        mesh_axes = tuple(a for a in mesh_axes if a not in used)
        used.update(mesh_axes)
        if not mesh_axes:
            out.append(None)
        elif len(mesh_axes) == 1:
            out.append(mesh_axes[0])
        else:
            out.append(mesh_axes)
    # trim trailing Nones (canonical form)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def _divisible(shape, pspec: P, mesh: Mesh) -> bool:
    for dim, entry in zip(shape, tuple(pspec)):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        if dim % n != 0:
            return False
    return True


def spec_to_pspec(spec: ParamSpec, rules: dict, mesh: Mesh | None = None) -> P:
    """PartitionSpec for one ParamSpec; falls back to dropping mesh axes a
    dim is not divisible by (e.g. 10 heads on a 16-way model axis →
    replicate rather than fail)."""
    pspec = _axes_to_pspec(spec.axes, rules)
    if mesh is None or _divisible(spec.shape, pspec, mesh):
        return pspec
    # drop offending axes one dim at a time
    entries = list(tuple(pspec)) + [None] * (len(spec.shape) - len(tuple(pspec)))
    for i, entry in enumerate(entries):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        keep = []
        n = 1
        for a in axes:
            if spec.shape[i] % (n * mesh.shape[a]) == 0:
                keep.append(a)
                n *= mesh.shape[a]
        entries[i] = tuple(keep) if len(keep) > 1 else (keep[0] if keep else None)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def tree_pspecs(specs, rules: dict, mesh: Mesh | None = None):
    """Map a nested ParamSpec tree to a PartitionSpec tree."""
    return jax.tree_util.tree_map(
        lambda s: spec_to_pspec(s, rules, mesh), specs,
        is_leaf=lambda x: isinstance(x, ParamSpec))


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
