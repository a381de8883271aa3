"""RG-LRU linear recurrence as a Pallas TPU kernel.

Grid ``(batch, chunks)`` with the chunk dimension sequential; the hidden
state (one (1, W) row per batch element) is carried in VMEM scratch. Each
step loads a (Q × W) tile of per-step coefficients (a, b), widens it to
float32 in VMEM scratch, and walks its rows in order, h = a_t·h + b_t, with
one (1, W) load, multiply-add and store per row (``fori_loop`` over dynamic
row slices; Mosaic lowers no ``associative_scan`` inside a kernel). There
is no matmul in this op: it is bandwidth-bound, and the win is keeping the
state resident in VMEM instead of re-reading it per step. W tiles at the
128-lane register width.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["lru_scan_kernel"]


def _kernel(a_ref, b_ref, y_ref, h_scr, a_scr, b_scr, y_scr):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a_scr[...] = a_ref[0].astype(jnp.float32)       # (Q, W)
    b_scr[...] = b_ref[0].astype(jnp.float32)

    def rows(g, h):                                 # 8 rows per trip
        for i in range(8):
            t = pl.ds(g * 8 + i, 1)
            h = a_scr[t, :] * h + b_scr[t, :]
            y_scr[t, :] = h
        return h

    h_scr[...] = jax.lax.fori_loop(0, a_scr.shape[0] // 8, rows, h_scr[...])
    y_ref[0] = y_scr[...].astype(y_ref.dtype)


def lru_scan_kernel(a: jax.Array, b: jax.Array, *, chunk: int = 128,
                    interpret: bool = True) -> jax.Array:
    """a, b: (B, S, W); returns h: (B, S, W) with h_t = a_t h_{t-1} + b_t."""
    Bsz, S, W = a.shape
    Q = min(chunk, -(-S // 8) * 8)       # rows come in groups of 8
    pad = (-S) % Q
    if pad:
        # pad with identity steps (a=1, b=0) so the carry passes through
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)), constant_values=1)
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
    nc = a.shape[1] // Q

    y = pl.pallas_call(
        _kernel,
        grid=(Bsz, nc),
        in_specs=[
            pl.BlockSpec((1, Q, W), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, Q, W), lambda i, c: (i, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, Q, W), lambda i, c: (i, c, 0)),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        scratch_shapes=[pltpu.VMEM((1, W), jnp.float32)]
        + [pltpu.VMEM((Q, W), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
    return y[:, :S]
