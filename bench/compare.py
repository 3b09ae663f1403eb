"""The comparisons that decide ``correct``: numbers, each against a limit.

Training cells compare, against the plain reference that followed the same
first steps: each step's loss, the norm of the first gradient (worked out
from the optimizer's state after one step) and the norm of the parameters'
change after those steps. Norms are compared leaf by leaf, and a leaf's gap
is ``| |prog| - |ref| |`` over the larger of the reference's norm of that
leaf and of the median leaf, so that leaves whose gradient is all but zero
do not dominate. The worst leaf is the number.
"""
from __future__ import annotations

from typing import List, Sequence

import numpy as np

# a leaf whose reference gradient is under this share of the median
# leaf's moves under Adam by round-off alone; its change is not compared
ROUNDOFF_LEAF = 1e-3


def loss_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    """Largest relative gap over steps and parties."""
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    if not np.all(np.isfinite(prog)):
        return float("inf")
    return float(np.max(np.abs(prog - ref) / np.abs(ref)))


def norm_gap(prog: Sequence[float], ref: Sequence[float],
             include: Sequence[bool] = None) -> float:
    """Worst leaf's gap between two lists of leaf norms."""
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    keep = (np.ones(ref.shape, bool) if include is None
            else np.asarray(include, bool))
    if not np.all(np.isfinite(prog[keep])):
        return float("inf")
    den = np.maximum(ref[keep], np.median(ref[keep]))
    diff = np.abs(prog[keep] - ref[keep])
    # a leaf that neither side moved (den 0) agrees; one moved by the
    # program alone is as far off as it can be
    gap = np.where(den > 0, diff / np.where(den > 0, den, 1.0),
                   np.where(diff > 0, np.inf, 0.0))
    return float(np.max(gap))


def moved_leaves(ref_grad1: Sequence[float]) -> List[bool]:
    """Leaves whose reference gradient is not nought to rounding."""
    g = np.asarray(ref_grad1, np.float64)
    return list(g >= ROUNDOFF_LEAF * np.median(g))


def flat(per_party: Sequence[Sequence[float]]) -> List[float]:
    return [v for party in per_party for v in party]
