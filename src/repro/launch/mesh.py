"""Mesh construction: :func:`make_mesh` is the one place a mesh is built.

Every mesh carries Auto axis types, set explicitly: ``jax.make_mesh``
defaults to Explicit axes, which ``with_sharding_constraint`` (the
activation constraints of ``parallel/ctx.py``) refuses to name.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module does not touch jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; everything else sees the real device count.

Topology: TPU v5e pods of 256 chips. Single-pod mesh (16, 16) with axes
(data, model); two-pod mesh (2, 16, 16) with axes (pod, data, model) — the
leading `pod` axis maps onto the inter-pod DCI/optical links, so data-
parallel gradient reduction crosses pods once per step while model-parallel
collectives stay inside a pod's ICI torus.
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

__all__ = ["make_mesh", "make_production_mesh", "HW"]


# TPU v5e hardware constants (per chip), used by the roofline analysis.
HW = {
    "peak_flops_bf16": 197e12,     # FLOP/s
    "hbm_bw": 819e9,               # B/s
    "ici_bw": 50e9,                # B/s per link
    "hbm_bytes": 16 * 2**30,
}


def make_mesh(devices, shape: tuple[int, ...],
              axes: tuple[str, ...] = ("data", "model")) -> Mesh:
    """``devices`` laid out as ``shape`` (one entry may be -1), Auto axes."""
    devs = np.asarray(devices).reshape(shape)
    return Mesh(devs, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(jax.devices()[:math.prod(shape)], shape, axes)
