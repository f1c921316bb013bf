"""Pallas TPU kernel: fused L2-normalize x bank-matmul x streaming top-k.

The query hot path of speculative filtering (§3.4): each query granularity
scans the whole store once. Blocking over the bank keeps the (bq, bn) score
tile in VMEM; a running (bq, k) best-scores/ids pair is merged per step, so
the full (Q, N) score matrix never exists. HBM traffic = one pass over the
bank = roofline optimum for a single query batch.

Mosaic lowers neither ``lax.top_k`` nor a lane-interleaving reshape, so the
merge is k rounds of max-and-mask (``_merge_topk``) and int4 rows dequantize
into [even | odd] column order with the query permuted to match
(``_deinterleave``). The running best lives in a 128-lane scratch so every
concatenation stays lane-aligned; only its first k lanes are live.

``interpret`` is a required argument of every entry here: the dispatch in
``ops`` decides it, and nothing below guesses it from the backend.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128  # running-best width: one lane tile holds k <= 128 slots


def _merge_topk(best_s, best_i, s, ids, k: int):
    """Merge a scored block into the running best: k rounds of max-and-mask
    over [best | block]. Equals ``lax.top_k`` of that concatenation,
    including its tie-break (the lower position first — running best before
    the block). ``best_s`` lanes >= k hold -inf and are never selected."""
    cat_s = jnp.concatenate([best_s, s], axis=1)
    cat_i = jnp.concatenate([best_i, ids], axis=1)
    pos = jax.lax.broadcasted_iota(jnp.int32, cat_s.shape, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, best_s.shape, 1)
    new_s = jnp.full(best_s.shape, -jnp.inf, jnp.float32)
    new_i = jnp.full(best_i.shape, -1, jnp.int32)
    for r in range(k):
        m = jnp.max(cat_s, axis=1, keepdims=True)
        p = jnp.min(jnp.where(cat_s == m, pos, jnp.int32(2**31 - 1)), axis=1,
                    keepdims=True)
        hit = pos == p
        sel = jnp.max(jnp.where(hit, cat_i, jnp.int32(-2**31)), axis=1,
                      keepdims=True)
        new_s = jnp.where(lane == r, m, new_s)
        new_i = jnp.where(lane == r, sel, new_i)
        cat_s = jnp.where(hit, -jnp.inf, cat_s)
    return new_s, new_i


def _init_best(best_s, best_i, k: int, fill_id: int):
    lane = jax.lax.broadcasted_iota(jnp.int32, best_s.shape, 1)
    best_s[...] = jnp.where(lane < k, NEG_INF, -jnp.inf)
    best_i[...] = jnp.full_like(best_i, fill_id)


def _dequant(p):
    """(..., D2) int8 nibble rows -> (..., 2*D2) fp32 integer values in
    [low nibbles | high nibbles] column order (unscaled)."""
    p32 = p.astype(jnp.int32)
    return jnp.concatenate([(p32 << 28) >> 28, p32 >> 4],
                           axis=-1).astype(jnp.float32)


def _deinterleave(query: jax.Array) -> jax.Array:
    """Permute query columns to [even | odd], the order ``_dequant`` emits."""
    q = query.astype(jnp.float32)
    return jnp.concatenate([q[:, 0::2], q[:, 1::2]], axis=1)


def _rsqrt_norm(x):
    return jax.lax.rsqrt(jnp.maximum(jnp.sum(x * x, -1, keepdims=True), 1e-16))


def _topk_kernel(n_ref, q_ref, b_ref, s_out, i_out, best_s, best_i, *,
                 k: int, block_n: int, nn: int, normalize: bool):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_best(best_s, best_i, k, 0)

    q = q_ref[...].astype(jnp.float32)  # (bq, E)
    b = b_ref[...].astype(jnp.float32)  # (bn, E)
    if normalize:
        q = q * _rsqrt_norm(q)
        b = b * _rsqrt_norm(b)
    s = jax.lax.dot_general(q, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bn)
    ids = j * block_n + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    # n_ref is a runtime scalar (SMEM), so the same compiled kernel serves
    # any fill level of a fixed-capacity bank slab
    s = jnp.where(ids < n_ref[0], s, NEG_INF)
    best_s[...], best_i[...] = _merge_topk(best_s[...], best_i[...], s, ids,
                                           k)

    @pl.when(j == nn - 1)
    def _final():
        s_out[...] = best_s[...]
        i_out[...] = best_i[...]


def _topk_int4_kernel(n_ref, q_ref, p_ref, sc_ref, s_out, i_out, best_s,
                      best_i, *, k: int, block_n: int, nn: int,
                      normalize: bool):
    """Fused dequant-and-scan: the bank block arrives as packed int4 nibbles
    (bn, E//2) + its per-row scales as one (1, bn) lane row, and is
    dequantized in VMEM right before the matmul — the fp32 bank never exists
    in HBM, so bank traffic is 8x lower than the dense kernel (int4 vs
    fp32). The scale multiplies the score column after the dot (a (bn, 1)
    scale block would make XLA relayout the whole scale vector to 128 lanes
    per row); normalization divides it out, so it then uses the raw nibbles."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_best(best_s, best_i, k, 0)

    q = q_ref[...]                                  # (bq, E), deinterleaved
    b = _dequant(p_ref[...])                        # (bn, E) fp32, VMEM only
    if normalize:
        q = q * _rsqrt_norm(q)
        b = b * _rsqrt_norm(b)
    s = jax.lax.dot_general(q, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bn)
    if not normalize:
        s = s * sc_ref[...]
    ids = j * block_n + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(ids < n_ref[0], s, NEG_INF)
    best_s[...], best_i[...] = _merge_topk(best_s[...], best_i[...], s, ids,
                                           k)

    @pl.when(j == nn - 1)
    def _final():
        s_out[...] = best_s[...]
        i_out[...] = best_i[...]


def _topk_int4_gather_kernel(n_ref, q_ref, p_ref, sc_ref, id_ref, s_out,
                             i_out, best_s, best_i, *, k: int, nl: int):
    """Fused dequant-and-scan over PRE-GATHERED per-query candidate rows
    (the IVF pruned-search hot path): each grid step sees a (bq, bl, E//2)
    int4 block of one query-group's candidates plus the candidates' global
    row ids. Each query's rows dequantize in VMEM and meet that query in
    one (1, E) x (bl, E) dot — Mosaic lowers no batched ``dot_general`` —
    with the same dequant-then-scale arithmetic as ``_topk_int4_kernel``.
    Candidates with id < 0 (padding) or id >= n_ref (rows past the scanned
    snapshot's fill) are masked to NEG_INF."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_best(best_s, best_i, k, -1)

    q = q_ref[...]                                  # (bq, E), deinterleaved
    bq, bl, _ = p_ref.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (bq, bl), 0)
    s = jnp.zeros((bq, bl), jnp.float32)
    for r in range(bq):
        sr = jax.lax.dot_general(q[r:r + 1], _dequant(p_ref[r]),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        s = jnp.where(row == r, sr, s)
    s = s * sc_ref[...]
    ids = id_ref[...]                               # (bq, bl) int32
    s = jnp.where((ids >= 0) & (ids < n_ref[0]), s, NEG_INF)
    best_s[...], best_i[...] = _merge_topk(best_s[...], best_i[...], s, ids,
                                           k)

    @pl.when(j == nl - 1)
    def _final():
        s_out[...] = best_s[...]
        i_out[...] = best_i[...]


def _check_k(k: int) -> None:
    if not 0 < k <= LANES:
        raise ValueError(f"Pallas retrieval top-k needs 0 < k <= {LANES}, "
                         f"got k={k}")


def _best_specs(bq: int, nrows: int):
    """Out specs, out shapes and scratch of the 128-lane running best."""
    spec = pl.BlockSpec((bq, LANES), lambda i, j: (i, 0))
    return ([spec, spec],
            [jax.ShapeDtypeStruct((nrows, LANES), jnp.float32),
             jax.ShapeDtypeStruct((nrows, LANES), jnp.int32)],
            [pltpu.VMEM((bq, LANES), jnp.float32),
             pltpu.VMEM((bq, LANES), jnp.int32)])


def retrieval_topk_int4_gathered_pallas(
        query: jax.Array, gathered: jax.Array, gscales: jax.Array,
        row_ids: jax.Array, k: int, *, interpret: bool, block_q: int = 8,
        block_l: int = 1024, n_valid=None) -> Tuple[jax.Array, jax.Array]:
    """Pruned-scan kernel entry: ``gathered`` (Q, L, E//2) int4 candidate
    rows + ``gscales`` (Q, L, 1) already gathered per query (the gather is
    int4-sized XLA work done by the dispatch wrapper inside the same jit),
    ``row_ids`` (Q, L) the candidates' global slab rows (-1 = padding).
    ``n_valid`` masks ids past the scanned snapshot's fill. Returns
    ((Q, k) scores, (Q, k) global row ids) — dead slots (pad or masked)
    carry the uniform sentinel pair score -1e30 / id -1, matching the
    ref/blocked variants."""
    _check_k(k)
    Q, L, E2 = gathered.shape
    E = query.shape[1]
    query = _deinterleave(query)
    gscales = gscales.reshape(Q, L)
    bq = min(block_q, Q)
    bl = min(block_l, L)
    padq = (-Q) % bq
    padl = (-L) % bl
    if padq:
        query = jnp.pad(query, ((0, padq), (0, 0)))
        gathered = jnp.pad(gathered, ((0, padq), (0, 0), (0, 0)))
        gscales = jnp.pad(gscales, ((0, padq), (0, 0)))
        row_ids = jnp.pad(row_ids, ((0, padq), (0, 0)), constant_values=-1)
    if padl:
        gathered = jnp.pad(gathered, ((0, 0), (0, padl), (0, 0)))
        gscales = jnp.pad(gscales, ((0, 0), (0, padl)))
        row_ids = jnp.pad(row_ids, ((0, 0), (0, padl)), constant_values=-1)
    nq = query.shape[0] // bq
    nl = row_ids.shape[1] // bl
    n_arr = jnp.full((1,), 2**31 - 1 if n_valid is None else n_valid,
                     jnp.int32)
    kernel = functools.partial(_topk_int4_gather_kernel, k=k, nl=nl)
    out_specs, out_shape, scratch = _best_specs(bq, query.shape[0])
    scores, ids = pl.pallas_call(
        kernel,
        grid=(nq, nl),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((bq, E), lambda i, j: (i, 0)),
                  pl.BlockSpec((bq, bl, E2), lambda i, j: (i, j, 0)),
                  pl.BlockSpec((bq, bl), lambda i, j: (i, j)),
                  pl.BlockSpec((bq, bl), lambda i, j: (i, j))],
        out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
        interpret=interpret,
    )(n_arr, query, gathered, gscales, row_ids)
    scores, ids = scores[:Q, :k], ids[:Q, :k]
    # dead-slot contract (shared with ref/blocked): a masked candidate's
    # real id must not survive next to a sentinel score
    return scores, jnp.where(scores > NEG_INF / 2, ids, -1)


def retrieval_topk_int4_pallas(query: jax.Array, packed: jax.Array,
                               scales: jax.Array, k: int, *, interpret: bool,
                               normalize: bool = False, block_q: int = 128,
                               block_n: int = 1024,
                               n_valid=None) -> Tuple[jax.Array, jax.Array]:
    """Packed-int4 variant of ``retrieval_topk_pallas``: ``packed`` is the
    (N, E//2) int8 nibble slab, ``scales`` the (N, 1) per-row absmax scales
    (``repro.core.quantize.quantize_int4`` layout). Same capacity-padding
    contract as the dense kernel (``n_valid`` masks rows past the fill)."""
    _check_k(k)
    Q, E = query.shape
    N, E2 = packed.shape
    query = _deinterleave(query)
    scales = scales.reshape(1, N)
    bq = min(block_q, Q)
    bn = min(block_n, N)
    padq = (-Q) % bq
    padn = (-N) % bn
    if padq:
        query = jnp.pad(query, ((0, padq), (0, 0)))
    if padn:
        packed = jnp.pad(packed, ((0, padn), (0, 0)))
        scales = jnp.pad(scales, ((0, 0), (0, padn)))
    nq = query.shape[0] // bq
    nn = packed.shape[0] // bn
    n_arr = jnp.full((1,), N if n_valid is None else n_valid, jnp.int32)
    kernel = functools.partial(_topk_int4_kernel, k=k, block_n=bn, nn=nn,
                               normalize=normalize)
    out_specs, out_shape, scratch = _best_specs(bq, query.shape[0])
    scores, ids = pl.pallas_call(
        kernel,
        grid=(nq, nn),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((bq, E), lambda i, j: (i, 0)),
                  pl.BlockSpec((bn, E2), lambda i, j: (j, 0)),
                  pl.BlockSpec((1, bn), lambda i, j: (0, j))],
        out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
        interpret=interpret,
    )(n_arr, query, packed, scales)
    return scores[:Q, :k], ids[:Q, :k]


def retrieval_topk_pallas(query: jax.Array, bank: jax.Array, k: int, *,
                          interpret: bool, normalize: bool = True,
                          block_q: int = 128, block_n: int = 1024,
                          n_valid=None) -> Tuple[jax.Array, jax.Array]:
    """``n_valid`` (int or traced int scalar, default = all of ``bank``)
    masks rows past the fill level of a fixed-capacity bank slab: passing the
    whole slab + a runtime count keeps the traced shapes stable between slab
    doublings, so serving inserts don't force a recompile per store size."""
    _check_k(k)
    Q, E = query.shape
    N = bank.shape[0]
    bq = min(block_q, Q)
    bn = min(block_n, N)
    padq = (-Q) % bq
    padn = (-N) % bn
    if padq:
        query = jnp.pad(query, ((0, padq), (0, 0)))
    if padn:
        bank = jnp.pad(bank, ((0, padn), (0, 0)))
    nq = query.shape[0] // bq
    nn = bank.shape[0] // bn
    n_arr = jnp.full((1,), N if n_valid is None else n_valid, jnp.int32)
    kernel = functools.partial(_topk_kernel, k=k, block_n=bn, nn=nn,
                               normalize=normalize)
    out_specs, out_shape, scratch = _best_specs(bq, query.shape[0])
    scores, ids = pl.pallas_call(
        kernel,
        grid=(nq, nn),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((bq, E), lambda i, j: (i, 0)),
                  pl.BlockSpec((bn, E), lambda i, j: (j, 0))],
        out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
        interpret=interpret,
    )(n_arr, query, bank)
    return scores[:Q, :k], ids[:Q, :k]
