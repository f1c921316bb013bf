"""The numbers ``correct`` compares, as plain functions of what the timed
path produced and what the reference gives for the same inputs.

* ``emb_gap``: the widest L2 distance between two sets of unit embeddings,
  row by row.
* ``rel_gap``: the widest relative Frobenius distance, row by row.
* ``scan_gap``: for a top-k scan, per query row and rank, the larger of how
  far the returned row's true score falls below the true k-th best at
  that rank and how far the returned score is off the true score, over
  the row's best true score.
* ``rank_gap``: the same for a final ranking over a candidate set, scored
  with the reference's vectors and query.
* ``mismatch``: rows that are not bitwise equal.
"""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np


def emb_gap(a, b) -> float:
    return float(np.max(np.linalg.norm(np.asarray(a, np.float64) -
                                       np.asarray(b, np.float64), axis=-1)))


def rel_gap(a, b) -> float:
    a = np.asarray(a, np.float64).reshape(len(a), -1)
    b = np.asarray(b, np.float64).reshape(len(b), -1)
    return float(np.max(np.linalg.norm(a - b, axis=1) /
                        np.linalg.norm(b, axis=1)))


def mismatch(a, b) -> int:
    if len(a) != len(b):
        return max(len(a), len(b))
    if len(a) == 0:
        return 0
    a = np.asarray(a, np.float32).reshape(len(a), -1)
    b = np.asarray(b, np.float32).reshape(len(b), -1)
    return int(np.sum(~np.all(a == b, axis=1)))


def scan_gap(best_scores: np.ndarray, true_of_returned: np.ndarray,
             returned_scores: np.ndarray) -> float:
    """``best_scores`` (Q, k): the exact scan's scores, descending;
    ``true_of_returned``: the exact score of each returned row;
    ``returned_scores``: the scores the scan returned."""
    top = np.maximum(np.abs(best_scores[:, :1]), 1e-6)
    short = (best_scores - true_of_returned) / top
    err = np.abs(returned_scores - true_of_returned) / top
    return float(max(short.max(), err.max()))


def rank_gap(uids: Sequence[int], scores: Sequence[float],
             s_ref: Dict[int, float], k: int) -> float:
    """One query's final ranking (``uids``, ``scores``) against the
    reference scores ``s_ref`` of its candidates; a returned uid that is
    not a candidate, or a list of the wrong length, reads 1."""
    ref_sorted = np.sort(np.array(list(s_ref.values()), np.float64))[::-1]
    if len(uids) != min(k, len(ref_sorted)):
        return 1.0
    top = max(abs(ref_sorted[0]), 1e-6) if len(ref_sorted) else 1.0
    worst = 0.0
    for r, (u, s) in enumerate(zip(list(uids), list(scores))):
        if int(u) not in s_ref:
            return 1.0
        ref = s_ref[int(u)]
        worst = max(worst, (ref_sorted[r] - ref) / top, abs(s - ref) / top)
    return float(worst)
