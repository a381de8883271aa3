"""Numerics for the plain references: float32 at "highest" precision, and
the control, computed one precision below the configuration's bfloat16:
every operand of every matrix product and every activation the program
holds in bfloat16 rounded to float8 e4m3 with one scale per tensor, as an
fp8 deployment of a bfloat16 model would.

The rounding is written out with ``frexp``/``ldexp`` so that it means the
same on every backend: 3 mantissa bits, a largest value of 448, and steps of
2**-9 below the smallest normal 2**-6.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

FP8_MAX = 448.0
FP8_MANTISSA_BITS = 3
FP8_MIN_EXP = -6          # smallest normal exponent of e4m3


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def mm_f32(spec: str, a, b):
    """The reference's product: float32 operands, float32 accumulation."""
    return _einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32))


def round_fp8(x):
    """``x`` rounded to the e4m3 grid after scaling its largest magnitude to
    448, then scaled back; float32 in and out."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    y = x / scale
    _, e = jnp.frexp(y)                       # |y| = m * 2**e, m in [0.5, 1)
    e = jnp.maximum(e - 1, FP8_MIN_EXP)       # exponent of the leading bit
    step = jnp.ldexp(jnp.ones_like(y), e - FP8_MANTISSA_BITS)
    q = jnp.clip(jnp.round(y / step) * step, -FP8_MAX, FP8_MAX)
    return q * scale


def mm_fp8(spec: str, a, b):
    """The control's product: every operand rounded to fp8, float32 sums."""
    return _einsum(spec, round_fp8(a), round_fp8(b))


class Numerics(NamedTuple):
    mm: Callable          # mm(spec, a, b): a matrix product
    act: Callable         # act(x): an activation as the precision holds it


F32 = Numerics(mm_f32, lambda x: x)
FP8 = Numerics(mm_fp8, round_fp8)
