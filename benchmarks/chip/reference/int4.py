"""The store's int4 rule and an exhaustive top-k scan, in plain numpy.

int4 rule: per-row absmax scale ``max|x| / 7`` (floored at 1e-12), values
rounded half-to-even and clipped to [-8, 7], two values packed per byte
(even index in the low nibble). Dequantized value = code * scale, in
float32.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np


def quantize(x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(..., D) -> (codes (..., D) int8 in [-8, 7], scale (..., 1) f32)."""
    xf = np.asarray(x, np.float32)
    scale = np.max(np.abs(xf), axis=-1, keepdims=True) / np.float32(7.0)
    scale = np.maximum(scale, np.float32(1e-12))
    codes = np.clip(np.rint(xf / scale), -8, 7).astype(np.int8)
    return codes, scale


def roundtrip(x: np.ndarray) -> np.ndarray:
    """What the store holds for ``x``: dequantize(quantize(x)), float32."""
    codes, scale = quantize(x)
    return codes.astype(np.float32) * scale


def scan_topk(queries: np.ndarray, rows: np.ndarray, k: int, *,
              patch: Optional[Dict[int, np.ndarray]] = None,
              block: int = 65536) -> Tuple[np.ndarray, np.ndarray]:
    """Exact top-k by raw inner product over ``rows`` (N, E) float32, with
    row r read as ``patch[r]`` where given, in row blocks. Returns (row
    indices (Q, k), scores (Q, k)), descending."""
    q = np.asarray(queries, np.float32)
    best_s = np.full((len(q), 0), -np.inf, np.float32)
    best_i = np.zeros((len(q), 0), np.int64)
    patch = patch or {}
    for lo in range(0, len(rows), block):
        blk = rows[lo:lo + block]
        mine = [r for r in patch if lo <= r < lo + len(blk)]
        if mine:
            blk = np.array(blk)
            for r in mine:
                blk[r - lo] = patch[r]
        s = q @ blk.T
        kk = min(k, s.shape[1])
        part = np.argpartition(-s, kk - 1, axis=1)[:, :kk]
        cat_s = np.concatenate([best_s, np.take_along_axis(s, part, 1)], 1)
        cat_i = np.concatenate([best_i, part + lo], 1)
        kk = min(k, cat_s.shape[1])
        top = np.argpartition(-cat_s, kk - 1, axis=1)[:, :kk]
        best_s = np.take_along_axis(cat_s, top, 1)
        best_i = np.take_along_axis(cat_i, top, 1)
    order = np.argsort(-best_s, axis=1, kind="stable")
    return (np.take_along_axis(best_i, order, 1),
            np.take_along_axis(best_s, order, 1))


def verify(uids: np.ndarray, scores: np.ndarray, k: int) -> np.ndarray:
    """Round 2 of the query path, plainly: visit one query's candidates from
    every granularity in descending score (equal scores in candidate
    order), keep each uid at its first visit, and return the first k."""
    u = np.asarray(uids, np.int64).ravel().tolist()
    s = np.asarray(scores, np.float32).ravel().tolist()
    seen, out = set(), []
    for i in sorted(range(len(u)), key=lambda i: (-s[i], i)):
        if s[i] <= -5e29 or u[i] in seen:
            continue
        seen.add(u[i])
        out.append(u[i])
    return np.asarray(out[:k], np.int64)
