"""Sharding context: lets pure model code place logical-axis constraints
without threading mesh objects through every call.

`steps.make_*_step` enters :func:`sharding_ctx` around tracing; model code
calls :func:`constrain_logical(x, ("batch", "seq", "vocab"))` at activation
boundaries (embeddings, logits, MoE dispatch). Outside any context the call
is the identity, so single-device smoke tests pay nothing.

The logical-axis arithmetic lives here too, once: :func:`axes_to_pspec`
maps logical axes to a PartitionSpec, and :func:`fit_pspec` drops the mesh
axes a dim does not divide by. ``sharding.py`` and the step makers use both.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["sharding_ctx", "current", "axes_to_pspec", "fit_pspec",
           "constrain_logical"]

_TLS = threading.local()


@contextmanager
def sharding_ctx(mesh, rules: dict):
    prev = getattr(_TLS, "ctx", None)
    _TLS.ctx = (mesh, rules)
    try:
        yield
    finally:
        _TLS.ctx = prev


def current():
    """(mesh, rules) of the step being traced, or None outside one."""
    return getattr(_TLS, "ctx", None)


def axes_to_pspec(axes, rules: dict) -> P:
    """PartitionSpec for logical ``axes`` under ``rules``; a mesh axis may
    appear in at most one dim."""
    entries = []
    used: set[str] = set()
    for ax in axes:
        mesh_axes = tuple(a for a in (rules.get(ax, ()) or ()) if a not in used)
        used.update(mesh_axes)
        if not mesh_axes:
            entries.append(None)
        elif len(mesh_axes) == 1:
            entries.append(mesh_axes[0])
        else:
            entries.append(mesh_axes)
    return _trim(entries)


def fit_pspec(pspec: P, shape: tuple, mesh) -> P:
    """``pspec`` without the mesh axes a dim of ``shape`` does not divide
    by (10 heads on a 16-way model axis, a batch of 1): those dims are
    replicated rather than refused."""
    entries = list(tuple(pspec)) + [None] * (len(shape) - len(tuple(pspec)))
    for i, entry in enumerate(entries):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        keep, n = [], 1
        for a in axes:
            if shape[i] % (n * mesh.shape[a]) == 0:
                keep.append(a)
                n *= mesh.shape[a]
        entries[i] = tuple(keep) if len(keep) > 1 else (keep[0] if keep else None)
    return _trim(entries)


def _trim(entries: list) -> P:
    """Canonical form: no trailing ``None``."""
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def constrain_logical(x: jax.Array, axes: tuple) -> jax.Array:
    ctx = current()
    if ctx is None:
        return x
    mesh, rules = ctx
    pspec = fit_pspec(axes_to_pspec(axes, rules), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, pspec))
