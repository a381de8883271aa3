"""Compiles for a described TPU v5e: what the chip's compiler would refuse.

Nothing runs: each program is lowered against shapes placed on a device of
a described (not attached) ``v5e:2x2`` topology and compiled by the TPU
compiler that ships with JAX. This catches what interpret mode cannot —
block shapes off the (8, 128) tiling, ops Mosaic does not lower, programs
that overflow the chip's 16 GiB — at the widths the chip runs
(``chip_smoke.py``). The topology is described inside a fixture, never at
import, so every xdist worker collects the same tests and only the worker
given this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.rglru.kernel import lru_scan_kernel
from repro.kernels.ssd.kernel import ssd_kernel
from repro.launch.mesh import make_mesh
from repro.parallel import sharding as shd
from repro.parallel.steps import (abstract_batch, abstract_train_state,
                                  make_train_step)

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
