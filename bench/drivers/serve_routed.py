"""Serving driver for a model that routes each token to its top-k experts:
the ``serve`` driver's open loop, sample and float32 reference, with
``logit_gap`` read as the mean, over every served token of the sample, of
the gap by which the token's reference logit lies below the reference's best
at its position, where ``serve`` reads the widest such gap.

Why the mean: in a stack of several expert layers, bfloat16 rounding moves
some token's top-k choice across a near tie in some layer, the token takes
another expert, and its layer output changes by an amount of the order of
the output itself; at the cell's sizes one served token in several takes
such a turn. Each of those can land as far from the reference's best as a
token that the fp8 control puts first, so the widest gap over a few
thousand served tokens reads about alike for a sound program and the
control. The mean weighs how often and how far: a sound program's is an
order of magnitude below the control's.

Each run prints a ``{"phase": "gaps"}`` line beside ``serve``'s: the served
tokens' count, mean gap, share not at the reference's best, quartiles and
widest gap, and the mean gap over each half of the longest request's answer
(the far half decodes over the longest contexts); with the control, the
same of the token the fp8 reference puts first.
"""

from __future__ import annotations

import os

import numpy as np

from bench.lib import harness, lowp

serve = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "serve.py"))


def run(cell) -> None:
    """``serve``'s run, with this module's comparison in place of its
    ``reference_gaps`` while it runs."""
    kept = serve.reference_gaps
    serve.reference_gaps = mean_gaps
    try:
        serve.run(cell)
    finally:
        serve.reference_gaps = kept


def position_gaps(ref_logits: np.ndarray, served) -> np.ndarray:
    """ref_logits (n, V) at the positions that produced ``served`` (n,):
    each position's gap, 0 where the served token is the reference's best."""
    rows = np.arange(len(served))
    return ref_logits.max(axis=-1) - ref_logits[rows, np.asarray(served)]


def summary(gaps: list[np.ndarray]) -> dict:
    g = np.concatenate(gaps)
    q = np.quantile(g, [0.25, 0.5, 0.75, 0.9, 0.99])
    return {"tokens": int(g.size), "mean": float(g.mean()),
            "missed": float(np.mean(g > 0)),
            "quantiles": {k: float(v) for k, v in
                          zip(("25", "50", "75", "90", "99"), q)},
            "max": float(g.max())}


def mean_gaps(cell, picked: list, seed: int, control: bool = False):
    """(mean gap of the served tokens under the float32 reference, and with
    ``control`` the mean gap of the tokens the fp8 reference puts first at
    the same positions, else None)."""
    import functools

    import jax
    import jax.numpy as jnp

    if not picked:
        return None, None
    c, model, bucket = cell.config, cell.model, cell.traffic["check"]["bucket"]
    params = model.make_weights(c, seed, jnp.dtype(c["torch_dtype"]))
    ref = jax.jit(functools.partial(model.logits, c=c, num=lowp.F32))
    low = jax.jit(functools.partial(model.logits, c=c, num=lowp.FP8))
    gaps, low_gaps = [], []
    for req in picked:
        seq = req.prompt + req.generated[:-1]
        size = -(-len(seq) // bucket) * bucket
        toks = jnp.asarray(seq + [0] * (size - len(seq)), jnp.int32)
        lo, n = len(req.prompt) - 1, len(req.generated)
        rows = np.asarray(ref(params, toks)[lo:lo + n])
        gaps.append(position_gaps(rows, req.generated))
        if control:
            q = np.asarray(low(params, toks)[lo:lo + n])
            low_gaps.append(position_gaps(rows, q.argmax(axis=-1)))
    longest, half = gaps[0], len(gaps[0]) // 2         # picked[0] is longest
    out = {"phase": "gaps", "program": summary(gaps),
           "longest": {"prompt": len(picked[0].prompt),
                       "answer": len(longest),
                       "mean_near_half": float(longest[:half].mean())
                       if half else None,
                       "mean_far_half": float(longest[half:].mean())}}
    if control:
        out["control"] = summary(low_gaps)
    cell.emit(out)
    return out["program"]["mean"], \
        out["control"]["mean"] if control else None
