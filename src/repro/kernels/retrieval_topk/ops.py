"""Dispatch wrappers for fused retrieval top-k.

``retrieval_topk`` scans a dense fp32 bank; ``impl`` selects the backend:
  * ``"auto"`` (default) — the Pallas kernel on TPU (compiled) and CPU
    (interpreted), the jnp/XLA reference elsewhere.
  * ``"pallas"`` — force the Pallas kernel.
  * ``"xla"`` — force the jnp reference (normalize → matmul → lax.top_k).

``retrieval_topk_int4`` scans a *packed int4* bank (the device-resident
DeviceBank path) with in-flight dequantization — the fp32 bank never
materializes: ``"pallas"`` dequantizes in VMEM, ``"xla"`` is a blocked jnp
scan compiled everywhere, ``"ref"`` the dequant-all oracle.

``resolve_impl`` is the one place that turns ``impl="auto"`` and
``interpret=None`` into a concrete (backend, interpret mode) pair; every
entry below and ``DeviceBank`` go through it, and the kernels themselves
take ``interpret`` as a required argument.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.retrieval_topk.kernel import (
    retrieval_topk_int4_gathered_pallas, retrieval_topk_int4_pallas,
    retrieval_topk_pallas)
from repro.kernels.retrieval_topk.ref import (
    retrieval_topk_int4_blocked, retrieval_topk_int4_gathered_blocked,
    retrieval_topk_int4_gathered_reference, retrieval_topk_int4_reference,
    retrieval_topk_reference)


def resolve_impl(impl: Optional[str] = "auto",
                 interpret: Optional[bool] = None, *, int4: bool = True,
                 platform: Optional[str] = None
                 ) -> Tuple[str, Optional[bool]]:
    """Concrete (impl, interpret) for a scan on ``platform`` (default: the
    default backend). ``auto`` takes the compiled Pallas kernel on a TPU.
    Elsewhere the int4 scans take the blocked XLA scan (the interpreted
    kernel loses to it) and the dense scan the interpreted kernel (its
    correctness path). ``interpret`` is None for non-Pallas impls; for
    Pallas an explicit value wins, else the kernel is interpreted exactly
    when the platform is not a TPU."""
    platform = platform or jax.default_backend()
    if impl in (None, "auto"):
        if platform == "tpu" or (platform == "cpu" and not int4):
            impl = "pallas"
        else:
            impl = "xla"
    allowed = ("pallas", "xla", "ref") if int4 else ("pallas", "xla")
    if impl not in allowed:
        raise ValueError(f"unknown retrieval_topk impl: {impl!r}")
    if impl != "pallas":
        return impl, None
    return impl, (platform != "tpu") if interpret is None else bool(interpret)


@functools.lru_cache(maxsize=128)
def _jitted(impl: str, k: int, normalize: bool, kw: tuple):
    """Per-(impl, k, flags) jitted entry point. jax.jit's own cache then
    specializes per input shape; the valid-row count rides along as a traced
    scalar, so a fixed-capacity bank slab reuses one compilation across any
    fill level."""
    if impl == "pallas":
        def fn(query, bank, n_valid):
            return retrieval_topk_pallas(query, bank, k, normalize=normalize,
                                         n_valid=n_valid, **dict(kw))
    else:
        def fn(query, bank, n_valid):
            return retrieval_topk_reference(query, bank, k,
                                            normalize=normalize,
                                            n_valid=n_valid)
    return jax.jit(fn)


def retrieval_topk(query: jax.Array, bank: jax.Array, k: int, *,
                   normalize: bool = True, impl: str = "auto",
                   interpret: Optional[bool] = None, n_valid: Optional[int] = None,
                   **kw) -> Tuple[jax.Array, jax.Array]:
    """``n_valid`` restricts the scan to the first n_valid bank rows (for
    capacity-padded slabs); defaults to the whole bank."""
    impl, interpret = resolve_impl(impl, interpret, int4=False)
    if impl == "pallas":
        kw = dict(kw, interpret=interpret)
    # both backends take the valid-row count as a traced scalar so a
    # capacity-padded bank reuses one compilation across fill levels
    n_arr = jnp.asarray(bank.shape[0] if n_valid is None else n_valid,
                        jnp.int32)
    return _jitted(impl, k, normalize,
                   tuple(sorted(kw.items())))(query, bank, n_arr)


# ---------------------------------------------------------------------------
# Packed-int4 fused dequant-and-scan (device-resident bank path)
# ---------------------------------------------------------------------------


# ahead-of-time compiled executables, keyed by (dispatch key, arg shapes).
# Populated by ``warm_retrieval_topk_int4`` (the async bank refresher calls
# it for a grown bank BEFORE publishing, so the retrace+compile never lands
# on a query); ``retrieval_topk_int4`` serves from it when shapes match.
_AOT_INT4 = {}


def _int4_dispatch_key(impl, interpret, kw):
    impl, interpret = resolve_impl(impl, interpret)
    if impl == "pallas":
        kw = dict(kw, interpret=interpret)
    return impl, tuple(sorted(kw.items()))


def warm_retrieval_topk_int4(query_shape: Tuple[int, int],
                             packed_shape: Tuple[int, int], k: int, *,
                             normalize: bool = False, impl: str = "auto",
                             interpret: Optional[bool] = None, **kw) -> None:
    """AOT-compile the fused int4 scan for the given shapes WITHOUT
    executing it (``jit.lower().compile()`` doesn't populate jax's call
    cache, so the executable is parked in a side table the dispatch checks
    first). Compilation costs 10-20x a steady scan; doing it off the query
    path is the point — see ``DeviceBank.warm``."""
    impl, kwt = _int4_dispatch_key(impl, interpret, kw)
    key = (impl, k, normalize, kwt, tuple(query_shape), tuple(packed_shape))
    if key in _AOT_INT4:
        return
    while len(_AOT_INT4) >= 64:  # bound like _jitted_int4's lru: FIFO-evict
        _AOT_INT4.pop(next(iter(_AOT_INT4)))  # oldest = superseded capacity
    fn = _jitted_int4(impl, k, normalize, kwt)
    _AOT_INT4[key] = fn.lower(
        jax.ShapeDtypeStruct(tuple(query_shape), jnp.float32),
        jax.ShapeDtypeStruct(tuple(packed_shape), jnp.int8),
        jax.ShapeDtypeStruct((packed_shape[0], 1), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32)).compile()


@functools.lru_cache(maxsize=128)
def _jitted_int4(impl: str, k: int, normalize: bool, kw: tuple):
    if impl == "pallas":
        def fn(query, packed, scales, n_valid):
            return retrieval_topk_int4_pallas(query, packed, scales, k,
                                              normalize=normalize,
                                              n_valid=n_valid, **dict(kw))
    elif impl == "xla":
        def fn(query, packed, scales, n_valid):
            return retrieval_topk_int4_blocked(query, packed, scales, k,
                                               normalize=normalize,
                                               n_valid=n_valid, **dict(kw))
    else:
        def fn(query, packed, scales, n_valid):
            return retrieval_topk_int4_reference(query, packed, scales, k,
                                                 normalize=normalize,
                                                 n_valid=n_valid)
    return jax.jit(fn)


def retrieval_topk_int4(query: jax.Array, packed: jax.Array,
                        scales: jax.Array, k: int, *,
                        normalize: bool = False, impl: str = "auto",
                        interpret: Optional[bool] = None,
                        n_valid: Optional[int] = None,
                        **kw) -> Tuple[jax.Array, jax.Array]:
    """Fused top-k over a packed int4 bank: ``packed`` (N, E//2) int8 nibble
    rows + ``scales`` (N, 1) per-row absmax (``quantize_int4`` layout). The
    fp32 bank is never materialized: rows dequantize block-wise right before
    scoring. ``impl``: 'pallas' (TPU kernel / interpret), 'xla' (blocked jnp
    scan, compiled everywhere), 'ref' (dequant-all oracle), or 'auto'."""
    impl, kwt = _int4_dispatch_key(impl, interpret, kw)
    n_arr = jnp.asarray(packed.shape[0] if n_valid is None else n_valid,
                        jnp.int32)
    aot = _AOT_INT4.get((impl, k, normalize, kwt, tuple(query.shape),
                         tuple(packed.shape)))
    if aot is not None:
        return aot(jnp.asarray(query, jnp.float32), packed,
                   jnp.asarray(scales, jnp.float32), n_arr)
    return _jitted_int4(impl, k, normalize, kwt)(query, packed, scales,
                                                 n_arr)


# ---------------------------------------------------------------------------
# Gathered (IVF pruned-search) fused dequant-and-scan
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=128)
def _jitted_int4_gathered(impl: str, k: int, normalize: bool, kw: tuple):
    """One jitted entry per (impl, k, flags). The candidate gather runs
    INSIDE the jit so the gathered rows stay int4 (the fp32 bank never
    materializes on any path): the pallas variant gathers with XLA then
    dequantizes in VMEM; the xla variant streams gather+dequant per block."""
    if impl == "pallas":
        def fn(query, packed, scales, row_ids, n_valid):
            safe = jnp.clip(row_ids, 0, packed.shape[0] - 1)
            gp = jnp.take(packed, safe, axis=0)     # (Q, L, E//2) int4 bytes
            gs = jnp.take(scales, safe, axis=0)     # (Q, L, 1)
            return retrieval_topk_int4_gathered_pallas(
                query, gp, gs, row_ids, k, n_valid=n_valid, **dict(kw))
    elif impl == "xla":
        def fn(query, packed, scales, row_ids, n_valid):
            return retrieval_topk_int4_gathered_blocked(
                query, packed, scales, row_ids, k, normalize=normalize,
                n_valid=n_valid, **dict(kw))
    else:
        def fn(query, packed, scales, row_ids, n_valid):
            return retrieval_topk_int4_gathered_reference(
                query, packed, scales, row_ids, k, normalize=normalize,
                n_valid=n_valid)
    return jax.jit(fn)


def retrieval_topk_int4_gathered(query: jax.Array, packed: jax.Array,
                                 scales: jax.Array, row_ids, k: int, *,
                                 normalize: bool = False, impl: str = "auto",
                                 interpret: Optional[bool] = None,
                                 n_valid: Optional[int] = None,
                                 **kw) -> Tuple[jax.Array, jax.Array]:
    """Fused top-k over per-query CANDIDATE rows of a packed int4 bank (the
    IVF pruned-search scan): ``row_ids`` (Q, L) int32 names each query's
    candidate slab rows, -1 entries are padding. Work and HBM traffic scale
    with L, not the bank size. Same (packed, scales) layout and dispatch
    contract as ``retrieval_topk_int4``; ``n_valid`` additionally masks ids
    past a snapshot's fill level (posting lists can run ahead of a stale
    bank generation). Returns ((Q, k) scores, (Q, k) GLOBAL row ids);
    slots with no live candidate score -1e30 (callers map them to uid -1).
    The ``normalize`` flag is honored by the xla/ref paths only (the store
    scans with raw inner products everywhere)."""
    impl, kwt = _int4_dispatch_key(impl, interpret, kw)
    if impl == "pallas" and normalize:
        raise ValueError("gathered pallas path scans raw inner products; "
                         "normalize=True is only supported on impl='xla'/"
                         "'ref'")
    row_ids = jnp.asarray(row_ids, jnp.int32)
    if row_ids.shape[1] < k:  # top-k needs >= k columns; -1 pads are masked
        row_ids = jnp.pad(row_ids,
                          ((0, 0), (0, k - row_ids.shape[1])),
                          constant_values=-1)
    n_arr = jnp.asarray(packed.shape[0] if n_valid is None else n_valid,
                        jnp.int32)
    return _jitted_int4_gathered(impl, k, normalize, kwt)(
        query, packed, scales, row_ids, n_arr)


@functools.lru_cache(maxsize=128)
def _jitted_int4_rows(impl: str, k: int, normalize: bool, kw: tuple):
    """Batch-shared candidate scan: gather the (padded) candidate rows ONCE
    for the whole query batch — int4-sized traffic — then run the standard
    fused dequant-and-scan over the gathered slab. Reuses the exhaustive
    kernels verbatim (pallas dequants the gathered block in VMEM), so the
    per-row arithmetic is identical to the full scan's."""
    if impl == "pallas":
        def fn(query, packed, scales, rows, m):
            gp = jnp.take(packed, rows, axis=0)
            gs = jnp.take(scales, rows, axis=0)
            return retrieval_topk_int4_pallas(query, gp, gs, k,
                                              normalize=normalize,
                                              n_valid=m, **dict(kw))
    elif impl == "xla":
        def fn(query, packed, scales, rows, m):
            gp = jnp.take(packed, rows, axis=0)
            gs = jnp.take(scales, rows, axis=0)
            return retrieval_topk_int4_blocked(query, gp, gs, k,
                                               normalize=normalize,
                                               n_valid=m, **dict(kw))
    else:
        def fn(query, packed, scales, rows, m):
            gp = jnp.take(packed, rows, axis=0)
            gs = jnp.take(scales, rows, axis=0)
            return retrieval_topk_int4_reference(query, gp, gs, k,
                                                 normalize=normalize,
                                                 n_valid=m)
    return jax.jit(fn)


def pow2_bucket(m: int, *, floor: int = 1, refine_above: int = 8192) -> int:
    """Shape bucket for dynamically-sized candidate sets: the next power of
    two >= max(m, floor), refined with a 3/4 step above ``refine_above``
    (scan cost tracks the PADDED size, so a 21k union should not pay for
    32k rows; still only ~2 traced shapes per octave). Shared by the
    batch-union scan and the sharded candidate partitioning so both retrace
    O(log) distinct shapes as unions grow."""
    m = max(int(m), int(floor), 1)
    bucket = 1 << (m - 1).bit_length()
    if bucket >= refine_above and m <= 3 * bucket // 4:
        bucket = 3 * bucket // 4
    return bucket


def retrieval_topk_int4_rows(query: jax.Array, packed: jax.Array,
                             scales: jax.Array, rows, k: int, *,
                             normalize: bool = False, impl: str = "auto",
                             interpret: Optional[bool] = None,
                             **kw) -> Tuple[jax.Array, jax.Array]:
    """Fused top-k over ONE shared candidate-row set for the whole query
    batch (the IVF batch-union strategy): ``rows`` (m,) int32 names the
    candidate slab rows, shared by every query. The rows are padded to a
    power-of-two bucket here (pad slots masked via the kernels' n_valid
    scalar, so the jit retraces O(log) shapes as the union grows) and
    gathered inside the jit. Returns ((Q, k) scores, (Q, k) LOCAL indices
    into ``rows``) — callers map back via ``rows[ids]``. Requires
    ``k <= len(rows)``."""
    impl, kwt = _int4_dispatch_key(impl, interpret, kw)
    rows = np.asarray(rows, np.int32).ravel()
    m = rows.size
    assert 0 < k <= m, (k, m)
    bucket = pow2_bucket(m, floor=k)
    if bucket > m:  # pad slots gather row 0 and are masked by n_valid=m
        rows = np.concatenate([rows, np.zeros(bucket - m, np.int32)])
    return _jitted_int4_rows(impl, k, normalize, kwt)(
        query, packed, scales, jnp.asarray(rows),
        jnp.asarray(m, jnp.int32))
