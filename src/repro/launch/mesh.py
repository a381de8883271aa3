"""Mesh construction: :func:`make_mesh` is the one place a mesh is built.

Every mesh carries Auto axis types, set explicitly: ``jax.make_mesh``
defaults to Explicit axes, which ``with_sharding_constraint`` (the
activation constraints of ``parallel/ctx.py``) refuses to name.
"""

from __future__ import annotations

import numpy as np
from jax.sharding import AxisType, Mesh

__all__ = ["make_mesh"]


def make_mesh(devices, shape: tuple[int, ...],
              axes: tuple[str, ...] = ("data", "model")) -> Mesh:
    """``devices`` laid out as ``shape`` (one entry may be -1), Auto axes."""
    devs = np.asarray(devices).reshape(shape)
    return Mesh(devs, axes, axis_types=(AxisType.Auto,) * len(axes))
