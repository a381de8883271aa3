"""Mamba-2 SSD chunked scan as a Pallas TPU kernel.

TPU adaptation of the SSD algorithm: grid ``(batch, heads, chunks)`` with the
chunk dimension sequential ("arbitrary") so the (P × N) inter-chunk state
lives in VMEM scratch and never round-trips HBM — the GPU implementation's
separate state-passing kernel collapses into the grid carry. Per step the
kernel streams one (Q × P) x-tile and (Q × N) B/C-tiles into VMEM, evaluates
the intra-chunk quadratic form on the MXU (Q×N @ N×Q and Q×Q @ Q×P matmuls,
Q and N chosen 128-aligned), and updates the carried state with one more
MXU product. All state math is float32; a_t = exp(dt·A) < 1 keeps every
decay factor in (0,1], so no log-space rescue is needed.

Layout: the wrapper moves heads ahead of the sequence, ``x`` (B,S,H,P) →
(B,H,S,P), so every block's last two dims are (Q, P) and meet the (8, 128)
tiling rule (P equals the full dim). The per-chunk cumulative decay
``cum = cumsum(dt·A)`` is formed by XLA outside the kernel and handed in as
a column (Q, 1) and as a row (1, Q): the quadratic form needs both
orientations, and this keeps transposes and scans out of the kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_kernel"]


def _kernel(x_ref, dt_ref, cum_ref, cum_row_ref, b_ref, c_ref, y_ref,
            state_scr):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)              # (Q, P)
    dt = dt_ref[0, 0]                                # (Q, 1)
    cum = cum_ref[0, 0]                              # (Q, 1)
    cum_row = cum_row_ref[0, 0]                      # (1, Q)
    Bm = b_ref[0].astype(jnp.float32)                # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)                # (Q, N)
    # cum at the chunk's last step, (1, 1); a masked lane-sum rather than a
    # slice, which Mosaic cannot broadcast from lane Q-1
    lane = jax.lax.broadcasted_iota(jnp.int32, cum_row.shape, 1)
    last = lane == x.shape[0] - 1
    cum_end = jnp.sum(jnp.where(last, cum_row, 0.0), axis=1, keepdims=True)
    u = dt * x                                       # (Q, P)

    # intra-chunk quadratic form on the MXU
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (Q, Q)
    # mask inside the exponent (upper triangle would overflow exp and
    # poison the vjp with inf·0 — same guard as ref.py)
    iota_i = jax.lax.broadcasted_iota(jnp.int32, CB.shape, 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, CB.shape, 1)
    L = jnp.exp(jnp.where(iota_j <= iota_i, cum - cum_row, -jnp.inf))
    y_intra = jax.lax.dot_general(CB * L, u, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # (Q,P)

    # inter-chunk contribution of the carried state (P, N)
    state = state_scr[...]
    y_inter = jnp.exp(cum) * jax.lax.dot_general(
        Cm, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (Q, N)·(P, N)^T → (Q, P)

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h' = exp(cum_Q) h + Σ_j exp(cum_Q - cum_j) u_j ⊗ B_j
    ud = u * jnp.exp(cum_end - cum)                  # (Q, P)
    state_scr[...] = (jnp.exp(cum_end) * state
                      + jax.lax.dot_general(ud, Bm, (((0,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32))


def ssd_kernel(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
               Cm: jax.Array, *, chunk: int = 256,
               interpret: bool = True) -> jax.Array:
    """x: (B,S,H,P); dt: (B,S,H); A: (H,); Bm, Cm: (B,S,N) → y: (B,S,H,P)."""
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    T = x.shape[1]
    nc = T // Q

    dtf = jnp.swapaxes(dt.astype(jnp.float32), 1, 2)             # (B,H,T)
    dA = (dtf * A.astype(jnp.float32)[None, :, None]).reshape(Bsz, H, nc, Q)
    cum = jnp.cumsum(dA, axis=-1).reshape(Bsz, H, T)             # per chunk
    xt = jnp.swapaxes(x, 1, 2)                                   # (B,H,T,P)

    def col(width):
        return pl.BlockSpec((1, 1, Q, width), lambda b, h, c: (b, h, c, 0))

    y = pl.pallas_call(
        _kernel,
        grid=(Bsz, H, nc),
        in_specs=[
            col(P),
            col(1),
            col(1),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=col(P),
        out_shape=jax.ShapeDtypeStruct(xt.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xt, dtf[..., None], cum[..., None], cum[:, :, None, :], Bm, Cm)
    return jnp.swapaxes(y, 1, 2)[:, :S]
