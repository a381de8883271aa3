"""Compiles for a described TPU v5e: what the chip's compiler would refuse.

Nothing runs: each program is lowered against shapes placed on a device of
a described (not attached) ``v5e:2x2`` topology and compiled by the TPU
compiler that ships with JAX. This catches what interpret mode cannot —
block shapes off the (8, 128) tiling, ops Mosaic does not lower, programs
that overflow the chip's 16 GiB, a decode step that copies its KV cache —
at the widths the chip runs (``chip_smoke.py``). The topology is described
inside a fixture, never at import, so every xdist worker collects the same
tests and only the worker given this file loads the TPU library.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.rglru.kernel import lru_scan_kernel
from repro.kernels.ssd.kernel import ssd_kernel
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.parallel import sharding as shd
from repro.parallel.steps import (abstract_batch, abstract_train_state,
                                  make_serve_step, make_train_step)

HBM_BYTES = 16 * 2**30          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile is written to the persistent cache but can
    # never be read back without a chip: keep the cache off around them
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _on(device, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=SingleDeviceSharding(device))


S = 2048
KERNELS = {
    # granite-8b: 32 query heads, 8 kv heads, head_dim 128
    "flash_attention": (
        lambda q, k, v: flash_attention_kernel(q, k, v, causal=True,
                                               interpret=False),
        [((1, 32, S, 128),), ((1, 8, S, 128),), ((1, 8, S, 128),)]),
    # mamba2-130m: 24 heads of dim 64, state 128, chunk 256
    "ssd": (
        lambda x, dt, A, Bm, Cm: ssd_kernel(x, dt, A, Bm, Cm, chunk=256,
                                            interpret=False),
        [((2, S, 24, 64),), ((2, S, 24), jnp.float32), ((24,), jnp.float32),
         ((2, S, 128),), ((2, S, 128),)]),
    # recurrentgemma-2b: lru_width 2560
    "rglru": (
        lambda a, b: lru_scan_kernel(a, b, interpret=False),
        [((2, S, 2560),), ((2, S, 2560),)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(topo, name):
    fn, shapes = KERNELS[name]
    args = [_on(topo.devices[0], *s) for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mamba2_130m_train_step_fits_one_v5e(topo):
    """The full published config's train step at chip_smoke's batch."""
    cfg = configs.get("mamba2-130m")
    mesh = make_mesh(topo.devices[:1], (1, 1))
    step = make_train_step(cfg, mesh, shd.make_rules(multi_pod=False))
    compiled = step.lower(abstract_train_state(cfg),
                          abstract_batch(cfg, 8, S)).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert cfg.param_count() * 12 <= m.argument_size_in_bytes  # f32 p, mu, nu
    assert total < HBM_BYTES, total / 2**30


# Published widths with 4 stacked layers, at the granite-8b.serve_chat cell's
# 32 rows of 4096 positions: at smaller shapes the compiler may keep a cache
# in on-chip memory, or fuse a whole-layer copy it makes at served sizes, so
# a guard there would pass what the chip pays for. mixtral keeps 2 of its 8
# experts so the stage fits one chip, and a 1024-slot window so its cache is
# a ring buffer shorter than max_len. moonshot runs the
# moonlight-16b-a3b.serve_chat_b64 cell's stage (its dense layer and 4 MoE
# layers) at that cell's 64 rows of 8192 positions of latent cache.
DECODE_CFGS = {
    "granite-8b": (dict(num_layers=4), 32, 4096),
    "mixtral-8x22b": (dict(num_layers=4, num_experts=2, window=1024), 32, 4096),
    "mamba2-130m": (dict(num_layers=4), 32, 4096),
    "moonshot-v1-16b-a3b": (dict(num_layers=5), 64, 8192),
}
_INSTR = re.compile(r"= \w+\[([\d,]*)\]\S* ([\w-]+)\(")


def _compile_decode(cfg, mesh, B, max_len):
    step = make_serve_step(cfg, mesh, shd.make_rules(multi_pod=False),
                           global_batch=B, max_len=max_len)
    cache = M.abstract_cache(cfg, B, max_len)
    compiled = step.lower(
        M.abstract_params(cfg, jnp.bfloat16), cache,
        jax.ShapeDtypeStruct((B, 1), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32)).compile()
    return cache, compiled


def _decode_attention_calls(hlo: str) -> int:
    """The ragged decode-attention kernel's custom calls in ``hlo``."""
    return sum("tpu_custom_call" in line and "decode_attention" in line
               for line in hlo.splitlines())


@pytest.mark.parametrize("arch", sorted(DECODE_CFGS))
def test_decode_updates_cache_in_place(topo, arch):
    """The decode step writes its rows into the donated cache: no copy of
    a stacked cache leaf, no whole-layer write of an attention cache, and
    no temporary as large as one layer of the largest leaf. Its attention
    reads the cache with the ragged kernel, which takes the stacked leaves
    as they are."""
    fields, B, max_len = DECODE_CFGS[arch]
    cfg = configs.get(arch).replace(**fields)
    cache, compiled = _compile_decode(
        cfg, make_mesh(topo.devices[:1], (1, 1)), B, max_len)

    leaves = jax.tree_util.tree_leaves_with_path(cache)
    stacked = {leaf.shape for _, leaf in leaves}
    # attention k/v and latent rows change one row per batch row; a
    # recurrent state is rewritten whole, so its one-layer
    # dynamic-update-slice is the write
    rows = {leaf.shape for path, leaf in leaves
            if path[-1].key in ("k", "v", "latent")}
    ops = {(op, tuple(int(d) for d in dims.split(",") if d))
           for dims, op in _INSTR.findall(compiled.as_text())}
    assert not {(op, s) for op, s in ops
                if op in ("copy", "copy-start") and s in stacked}
    assert not {(op, s) for op, s in ops
                if op == "dynamic-update-slice" and s in rows}
    # one kernel call per stacked group of attention layers
    assert _decode_attention_calls(compiled.as_text()) == len(
        {path[0].key for path, _ in leaves if path[-1].key in ("k", "latent")})

    m = compiled.memory_analysis()
    nbytes = [leaf.size * leaf.dtype.itemsize for _, leaf in leaves]
    assert m.alias_size_in_bytes == sum(nbytes)          # donated, reused
    # one layer of the largest stacked leaf
    assert m.temp_size_in_bytes < max(
        n // leaf.shape[0] for n, (_, leaf) in zip(nbytes, leaves))


def test_kimi_linear_decode_keeps_its_cache_in_place(topo):
    """The kimi-linear-48b-a3b.serve_reason_b128 cell's decode (8 unrolled
    layers, 32 of 256 experts held, 128 rows of 4096): each KDA state and
    conv tail rewritten in its own buffer, each latent cache written in
    place (its rows in scatters of at most 1024 pieces: one of more copies
    the whole cache), one kernel call per latent-attention layer, no
    temporary as large as one layer's state, and the step beside a second
    cache (the splice's) within the chip."""
    cfg = configs.get("kimi-linear-48b-a3b").replace(num_layers=8,
                                                      experts_held=32)
    B, max_len = 128, 4096
    cache, compiled = _compile_decode(
        cfg, make_mesh(topo.devices[:1], (1, 1)), B, max_len)
    leaves = jax.tree_util.tree_leaves(cache)
    hlo = compiled.as_text()
    ops = {(op, tuple(int(d) for d in dims.split(",") if d))
           for dims, op in _INSTR.findall(hlo)}
    shapes = {leaf.shape for leaf in leaves}
    assert not {(op, s) for op, s in ops
                if op in ("copy", "copy-start") and s in shapes}
    assert _decode_attention_calls(hlo) == 2
    m = compiled.memory_analysis()
    nbytes = sum(leaf.size * leaf.dtype.itemsize for leaf in leaves)
    assert m.alias_size_in_bytes == nbytes
    state = min(leaf.size * leaf.dtype.itemsize for leaf in leaves
                if leaf.dtype == jnp.float32)
    assert m.temp_size_in_bytes < state
    assert m.argument_size_in_bytes + m.temp_size_in_bytes + nbytes < \
        HBM_BYTES


# granite's 8 KV heads split over the model axis; Moonlight's latent cache
# has no head axis, so its slots are split there and the shards' softmaxes
# merged
SHARDED_CFGS = {
    "granite-8b": (dict(num_layers=2), 32, 4096),
    "moonshot-v1-16b-a3b": (dict(num_layers=3), 64, 8192),
}
_COLLECTIVE = re.compile(
    r"= \(?\w+\[([\d,]*)\][^=]* (all-gather|all-reduce|all-to-all|"
    r"collective-permute)(?:-start)?\(")


@pytest.mark.parametrize("arch", sorted(SHARDED_CFGS))
def test_sharded_decode_reads_cache_shards_in_place(topo, arch):
    """On a 2 x 2 mesh the kernel runs per shard of the cache: apart from
    gathering weights, no collective moves as much as one layer of a
    cache shard."""
    fields, B, max_len = SHARDED_CFGS[arch]
    cfg = configs.get(arch).replace(**fields)
    mesh = make_mesh(topo.devices, (2, 2))
    cache, compiled = _compile_decode(cfg, mesh, B, max_len)
    hlo = compiled.as_text()
    assert _decode_attention_calls(hlo) >= 1
    layer_shard = min(leaf.size // leaf.shape[0] // mesh.size
                      for leaf in jax.tree_util.tree_leaves(cache))
    weights = {leaf.shape for leaf in
               jax.tree_util.tree_leaves(M.abstract_params(cfg))}
    moved = [(op, tuple(int(d) for d in dims.split(",") if d))
             for dims, op in _COLLECTIVE.findall(hlo)]
    assert moved
    assert all(math.prod(s) < layer_shard for _, s in moved
               if s not in weights), moved
