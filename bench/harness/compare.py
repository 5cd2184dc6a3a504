"""The comparison that decides ``correct``: every number beside its limit,
the run correct only when each is within it."""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np


def rel_gap(got: Sequence[float], ref: Sequence[float]) -> float:
    """Largest relative gap of paired scalars (one per step)."""
    if len(got) != len(ref):
        return float("inf")
    g, r = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if not np.all(np.isfinite(g)):
        return float("inf")
    return float(np.max(np.abs(g - r) / np.maximum(np.abs(r), 1e-30)))


def leaf_gap(got: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
             keep: Optional[Dict[str, np.ndarray]] = None) -> float:
    """Worst leaf: the gap between the program's norm of a leaf and the
    reference's (not the norm of their difference), over the larger of the
    reference's norm of that leaf and of the median leaf.  ``keep`` masks
    leaves out (those the reference's gradient leaves unmoved)."""
    if set(got) != set(ref):
        return float("inf")
    r_all = np.concatenate([np.ravel(ref[k]) for k in ref])
    med = float(np.median(r_all))
    worst = 0.0
    for k in ref:
        g, r = np.ravel(got[k]).astype(np.float64), np.ravel(ref[k])
        if g.shape != r.shape or not np.all(np.isfinite(g)):
            return float("inf")
        m = np.ones(r.shape, bool) if keep is None else np.ravel(keep[k])
        if not m.any():
            continue
        gap = np.abs(g - r) / np.maximum(np.maximum(r, med), 1e-30)
        worst = max(worst, float(np.max(gap[m])))
    return worst


def verdict(compared: List[Dict]) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in compared)
