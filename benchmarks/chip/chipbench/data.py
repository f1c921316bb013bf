"""Seeded data for every traffic mix: ingest items (patch features or token
ids), query token ids, bank filler rows, placed rows and the fresh rows'
cached activations.

All of it is a function of ``--seed`` alone. Large arrays are drawn on the
device in chunks keyed by ``fold_in(seed, tag, chunk)``, so any chunk can
be drawn again for the correctness check without keeping it on the host.
"""
from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

TAG_ITEMS, TAG_ACTS = 1, 2
TAG_TOKENS = 8                 # host draws: 3 query ids, 4 filler, 5-7 loops
FILLER_UID0 = 10 ** 9          # filler row i has uid FILLER_UID0 + i
FRESH_UID0 = 2 * 10 ** 9       # query i's fresh coarse row
PLACED_UID0 = 3 * 10 ** 9      # query i's fine row j: PLACED_UID0 + 16 i + j
FILLER_BLOCK = 65536


def rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2 ** 63, *tags])


@functools.partial(jax.jit, static_argnums=(1,))
def _normal_bf16(key, shape):
    return jax.random.normal(key, shape, jnp.bfloat16)


def device_normal_bf16(seed: int, tag: int, chunk: int, shape) -> np.ndarray:
    """(shape) standard normal bf16 from (seed, tag, chunk), on the host."""
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(int(seed) % 2 ** 32), tag), chunk)
    return np.asarray(_normal_bf16(key, tuple(shape)))


def item_pool(seed: int, n: int, n_tokens: int, d_input: int,
              chunk: int = 64) -> np.ndarray:
    """(n, n_tokens, d_input) bf16 patch features."""
    return np.concatenate([
        device_normal_bf16(seed, TAG_ITEMS, c, (min(chunk, n - c * chunk),
                                                n_tokens, d_input))
        for c in range(-(-n // chunk))])


def ingest_pool(seed: int, n: int, t: dict) -> np.ndarray:
    """``n`` seeded items for the tower ``t``: (n, n_tokens) int32 token ids
    for a vocabulary tower, patch features (``item_pool``) for a
    stub-frontend one."""
    if t["vocab"]:
        return rng(seed, TAG_TOKENS).integers(0, t["vocab"],
                                              (n, t["n_tokens"]),
                                              dtype=np.int32)
    return item_pool(seed, n, t["n_tokens"], t["d_input"])


def fresh_activations(seed: int, drain: int, batch: int, n_tokens: int,
                      d_model: int) -> np.ndarray:
    """Cached superficial states of drain ``drain``'s fresh rows:
    (batch, n_tokens + 1, d_model) bf16."""
    return device_normal_bf16(seed, TAG_ACTS, drain,
                              (batch, n_tokens + 1, d_model))


def query_ids(seed: int, drain: int, batch: int, n_tokens: int,
              vocab: int) -> np.ndarray:
    """Token ids of drain ``drain``'s queries: (batch, n_tokens) int32,
    a function of (seed, drain) only."""
    return rng(seed, 3, drain).integers(0, vocab, (batch, n_tokens),
                                        dtype=np.int32)


class Filler:
    """Bank filler rows: one seeded block of ``FILLER_BLOCK`` directions at
    ``norm``, tiled with seeded per-column sign patterns. Row i is block
    row ``i % FILLER_BLOCK`` times sign pattern ``i // FILLER_BLOCK``."""

    def __init__(self, seed: int, n: int, dim: int, norm: float):
        r = rng(seed, 4)
        self.n = n
        self.block = r.standard_normal((min(n, FILLER_BLOCK), dim),
                                       dtype=np.float32)
        self.block *= np.float32(norm) / np.linalg.norm(self.block, axis=1,
                                                        keepdims=True)
        n_tiles = -(-n // FILLER_BLOCK)
        self.signs = np.where(r.random((n_tiles, dim)) < 0.5, -1.0,
                              1.0).astype(np.float32)

    def tile(self, t: int) -> Tuple[np.ndarray, np.ndarray]:
        """(uids, rows) of tile t."""
        lo = t * FILLER_BLOCK
        m = min(FILLER_BLOCK, self.n - lo)
        return (np.arange(FILLER_UID0 + lo, FILLER_UID0 + lo + m),
                self.block[:m] * self.signs[t])

    def rows(self, uids: np.ndarray) -> np.ndarray:
        i = np.asarray(uids, np.int64) - FILLER_UID0
        return self.block[i % FILLER_BLOCK] * self.signs[i // FILLER_BLOCK]


def place_rows(q_embs: np.ndarray, own_score: float, ridge: float = 1e-3
               ) -> np.ndarray:
    """Row directions that score ``own_score`` for their own query's
    full-depth embedding and near 0 for every other (query, granularity).

    ``q_embs`` is (P, G, E), full depth last. Queries of a random tower sit
    in a narrow cone, so a row along a query scores high for all of them.
    Each row is instead that query's column of the ridge pseudo-inverse of
    all P*G query embeddings: r_i = c * M q_i / (q_i . M q_i) with
    M = (A^T A + lambda I)^-1, so q_j . r_i = c * H_ji / H_ii for the hat
    matrix H = A M A^T, whose off-diagonal entries are small."""
    P, G, E = q_embs.shape
    A = q_embs.reshape(P * G, E).astype(np.float64)
    C = A.T @ A
    C[np.diag_indices(E)] += ridge * np.trace(C) / E
    W = np.linalg.solve(C, q_embs[:, -1].astype(np.float64).T).T   # (P, E)
    own = np.sum(q_embs[:, -1] * W, axis=1)
    return (own_score * W / own[:, None]).astype(np.float32)


def placement_margin(q_embs: np.ndarray, R: np.ndarray, n_fine: int,
                     k: int) -> float:
    """How far every query's own placed rows stay above every other
    placed row, at any granularity, as a share of its own lowest score
    (computed on the device at float32). Rows are rounded by the int4 rule
    first; fine row j is row i scaled by 1 - 0.01 j."""
    from reference import int4 as R4
    P, G, E = q_embs.shape
    Rq = jnp.asarray(R4.roundtrip(R))
    lo_scale = 1.0 - 0.01 * n_fine
    own = np.sum(q_embs[:, -1] * R4.roundtrip(R * lo_scale), axis=1)
    worst = np.inf

    @jax.jit
    def block(qb, ids):
        s = jnp.einsum("qe,pe->qp", qb, Rq,
                       precision=jax.lax.Precision.HIGHEST)
        s = jnp.where(s > 0, s, s * lo_scale)
        s = jnp.where(jnp.arange(P)[None, :] == ids[:, None], -jnp.inf, s)
        return jnp.max(s, axis=1)

    flat = q_embs.reshape(P * G, E)
    ids = np.repeat(np.arange(P), G)
    for lo in range(0, P * G, 1024):
        qb, ib = flat[lo:lo + 1024], ids[lo:lo + 1024]
        pad = 1024 - len(qb)
        if pad:
            qb = np.concatenate([qb, np.zeros((pad, E), np.float32)])
            ib = np.concatenate([ib, np.full(pad, -1)])
        other = np.asarray(block(jnp.asarray(qb), jnp.asarray(ib)))
        other = other[:1024 - pad]
        worst = min(worst, float(np.min((own[ib[:1024 - pad]] - other) /
                                        own[ib[:1024 - pad]])))
    return worst
