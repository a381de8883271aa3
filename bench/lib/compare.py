"""The comparisons that decide ``correct`` for a served model: the widest gap
by which a served token's reference logit lies below the reference's best
logit at that position, and, for the control, the same gap of the token a
lower precision puts first."""

from __future__ import annotations

import numpy as np


def served_gap(ref_logits: np.ndarray, served: list[int]) -> float:
    """ref_logits (n, V) at the positions that produced ``served`` (n,)."""
    rows = np.arange(len(served))
    best = ref_logits.max(axis=-1)
    return float(np.max(best - ref_logits[rows, np.asarray(served)]))


def control_gap(ref_logits: np.ndarray, low_logits: np.ndarray) -> float:
    """The gap, under the reference, of the token the lower precision puts
    first, at each position; the widest."""
    return served_gap(ref_logits, low_logits.argmax(axis=-1).tolist())
