"""Slab-backed embedding store: coarse embeddings + exit metadata + INT4
activation cache.

Host-side component of the serving runtime — the analogue of the paper's
on-flash store (§5.4: ~5KB per 1024-d item at INT4 + overhead). Unlike the
seed's list-of-rows design, embeddings live in contiguous growable slabs:

  * ``_packed``  (cap, E//2) int8  — two INT4 nibbles per byte (or (cap, E)
    fp32 when ``store_int4=False``),
  * ``_scales``  (cap, 1)   fp32   — per-row absmax scales,
  * ``_meta``    (cap,) structured — uid / exit_idx / exit_layer / modality /
    fine, vectorized-queryable without touching Python objects,
  * ``_dense``   (cap, E)  fp32    — incrementally-maintained dequantized
    search matrix: only rows marked dirty by an insert/upgrade are
    re-dequantized (one jnp call per refresh), never the whole store.

Capacity grows by amortized doubling; a uid→row hash index replaces the
seed's O(N) scan. ``add_batch``/``upgrade_batch`` quantize whole batches in a
single jnp call instead of one device round-trip per item. Reads snapshot
(row data, uid index) pairs under the same lock as mutations, closing the
seed's torn row/metadata races; the search scan itself runs outside the lock
so queries don't serialize inserts (see ``_search_snapshot``).

``search_batch`` is the serving hot path. On accelerators ``impl='auto'``
resolves to the *device-resident* path: the int4 slab lives on-device as a
``DeviceBank`` (see ``repro.core.device_bank``), refreshed incrementally
from the dirty-row bitmap — zero full-slab H2D uploads after warm-up — and
scanned by the fused dequant-top-k kernel so neither the fp32 bank nor the
(Q, N) score matrix ever materializes. On CPU ``impl='auto'`` cuts over to
the numpy matmul path (the interpret-mode kernel loses to BLAS); the device
path still works there (``impl='device'``) and is what the tests exercise.
Quantization for inserts runs on the pure-numpy parity path
(``quantize_int4_np``): no device dispatch per ``add``/``add_batch``. Cached
activations handed over on the device (one ``jax.Array`` per item) are
quantized there by the bit-exact ``quantize_int4``: only codes and scales
cross.
Queried items are permanently upgraded to fine-grained embeddings (§5.3
"web cookie" rule) via ``upgrade``/``upgrade_batch``.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import spans
from repro.core.quantize import (dequantize_int4, quantize_int4,
                                 quantize_int4_np)

# cached states on the device, one (..., d) array per item: one program a
# group size
_quantize_items = jax.jit(lambda hs: quantize_int4(jnp.stack(hs)))


_META_DTYPE = np.dtype([("uid", np.int64), ("exit_idx", np.int32),
                        ("exit_layer", np.int32), ("fine", np.bool_),
                        ("modality_id", np.int32)])  # index into _modalities


@dataclasses.dataclass
class StoreEntry:
    """Back-compat row view (materialized on demand from the meta slab)."""
    uid: int
    exit_idx: int          # index into the exit list (not layer number)
    exit_layer: int        # layer depth of the coarse embedding
    modality: str
    fine: bool             # already refined to full depth?


class EmbeddingStore:
    def __init__(self, embed_dim: int, store_int4: bool = True,
                 capacity: int = 64):
        if store_int4:  # nibble packing needs an even dim; fp32 mode doesn't
            assert embed_dim % 2 == 0, embed_dim
        self.embed_dim = embed_dim
        self.store_int4 = store_int4
        self._row_width = embed_dim // 2 if store_int4 else embed_dim
        self._row_dtype = np.int8 if store_int4 else np.float32
        self._cap = max(int(capacity), 1)
        self._n = 0
        self._packed = np.zeros((self._cap, self._row_width), self._row_dtype)
        self._scales = np.ones((self._cap, 1), np.float32)
        self._meta = np.zeros(self._cap, _META_DTYPE)
        self._dense = np.zeros((self._cap, embed_dim), np.float32)
        self._dirty = np.zeros(self._cap, np.bool_)
        self._any_dirty = False
        # second dirty bitmap, consumed by the device bank's incremental
        # refresh (the dense cache and the bank sync independently)
        self._bank_dirty = np.zeros(self._cap, np.bool_)
        self._any_bank_dirty = False
        self._bank = None  # DeviceBank, created lazily / via attach
        # bounded-staleness accounting for the async refresh path: how many
        # distinct rows are dirty-but-unpublished, and since when
        self._bank_pending_rows = 0
        self._bank_first_dirty_t: Optional[float] = None
        self._bank_refresher = None  # RefreshScheduler in async mode
        # online IVF coarse-filter index (attach_ivf); mutations keep its
        # assignment/posting lists in lockstep under this same lock
        self._ivf = None
        self.ivf_fallbacks = 0  # impl='ivf' queries served exhaustively
        self._escaped_n = 0  # rows visible to views handed out to readers
        # re-upload accounting for the non-resident kernel paths (the bytes
        # the device bank exists to eliminate; see benchmarks/store_scale.py)
        self.upload_bytes = 0
        self.upload_calls = 0
        self._uid_to_row: Dict[int, int] = {}
        self._modalities: List[str] = [""]  # interned names; id 0 = unset
        # (packed, scale, shape, exit_layer) per uid; packed is (S, d//2) int8
        self._act_cache: Dict[int, Tuple[np.ndarray, np.ndarray, Tuple[int, ...], int]] = {}
        self._lock = threading.RLock()

    def _modality_id_locked(self, name: str) -> int:
        try:
            return self._modalities.index(name)
        except ValueError:
            self._modalities.append(name)
            return len(self._modalities) - 1

    # -- capacity ------------------------------------------------------------

    def _ensure_capacity(self, n_needed: int) -> None:
        if n_needed <= self._cap:
            return
        cap = self._cap
        while cap < n_needed:
            cap *= 2
        for name in ("_packed", "_scales", "_meta", "_dense", "_dirty",
                     "_bank_dirty"):
            old = getattr(self, name)
            new = np.zeros((cap,) + old.shape[1:], old.dtype)
            new[:self._n] = old[:self._n]
            setattr(self, name, new)
        self._cap = cap
        self._escaped_n = 0  # the fresh dense buffer has no outside readers
        if self._ivf is not None:
            self._ivf.ensure_capacity(cap)

    def _quantize_rows(self, embs: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """(B, E) fp32 -> (packed rows, scales), host-side: the numpy path is
        bit-exact with ``quantize_int4`` and costs zero device dispatches
        (a per-item ``add`` used to pay a jit round-trip here)."""
        if self.store_int4:
            with spans.span("store.quantize"):
                spans.count("quantized_bytes", embs.nbytes)
                return quantize_int4_np(embs)
        return embs, np.ones((len(embs), 1), np.float32)

    # -- mutation ------------------------------------------------------------

    def add(self, uid: int, emb: np.ndarray, *, exit_idx: int, exit_layer: int,
            modality: str = "", fine: bool = False,
            cached_h: Optional[np.ndarray] = None) -> None:
        self.add_batch([uid], np.asarray(emb, np.float32)[None],
                       [exit_idx], [exit_layer], modality=modality, fine=fine,
                       cached_hs=None if cached_h is None
                       else np.asarray(cached_h, np.float32)[None])

    @spans.spanned("store.add_batch")
    def add_batch(self, uids, embs, exit_idxs, exit_layers, *, modality="",
                  fine: bool = False, cached_hs=None) -> None:
        """Vectorized insert: one quantize call for the embedding batch and
        (optionally) one for the whole activation batch. Re-adding an
        existing uid overwrites its row in place (last write wins) instead of
        leaving a ghost duplicate in the slab."""
        uids = np.asarray(uids, np.int64).ravel()
        embs = np.asarray(embs, np.float32).reshape(len(uids), self.embed_dim)
        packed, scales = self._quantize_rows(embs)
        act = None
        if cached_hs is not None:  # (B, ..., d), or B (..., d) device arrays
            with spans.span("store.quantize"):
                if (isinstance(cached_hs, (list, tuple)) and cached_hs
                        and isinstance(cached_hs[0], jax.Array)):
                    # quantized where it lives: only codes and scales cross
                    spans.count("device_quantized_bytes",
                                sum(h.nbytes for h in cached_hs))
                    p, s = _quantize_items(tuple(cached_hs))
                    p, s = spans.to_host(p), spans.to_host(s)
                else:
                    ch = np.asarray(cached_hs, np.float32)
                    spans.count("quantized_bytes", ch.nbytes)
                    p, s = quantize_int4_np(ch)
            act = (p, s, p.shape[1:-1] + (2 * p.shape[-1],))
        exit_idxs = np.asarray(exit_idxs, np.int32).ravel()
        exit_layers = np.asarray(exit_layers, np.int32).ravel()
        with self._lock:
            mod_id = self._modality_id_locked(modality)
            rows = np.empty(len(uids), np.int64)
            nxt = self._n
            for j, u in enumerate(uids.tolist()):
                row = self._uid_to_row.get(u)
                if row is None:
                    row = nxt
                    nxt += 1
                    self._uid_to_row[u] = row
                elif act is None:
                    # re-add without fresh activations: evict the previous
                    # content's cache so refinement can't resume from it
                    self._act_cache.pop(u, None)
                rows[j] = row
            self._ensure_capacity(nxt)
            self._packed[rows] = packed
            self._scales[rows] = scales
            self._meta["uid"][rows] = uids
            self._meta["exit_idx"][rows] = exit_idxs
            self._meta["exit_layer"][rows] = exit_layers
            self._meta["modality_id"][rows] = mod_id
            self._meta["fine"][rows] = fine
            self._dirty[rows] = True
            self._any_dirty = True
            self._mark_bank_dirty_locked(rows)
            if act is not None:
                ap, ascale, shape = act
                for j, u in enumerate(uids.tolist()):
                    self._act_cache[u] = (ap[j], ascale[j], shape,
                                          int(exit_layers[j]))
            self._n = nxt
            if self._ivf is not None:  # train then assign, one argmin each
                self._ivf.observe(embs)
                self._ivf.assign_rows(rows, embs, nxt)

    def upgrade(self, uid: int, fine_emb: np.ndarray) -> None:
        """Permanently replace a coarse embedding with its refined version."""
        self.upgrade_batch([uid], np.asarray(fine_emb, np.float32)[None])

    @spans.spanned("store.upgrade_batch")
    def upgrade_batch(self, uids: Sequence[int], fine_embs: np.ndarray) -> None:
        """Vectorized §5.3 upgrade: requantize the whole batch in one call,
        mark only the touched rows dirty, free their activation cache."""
        uids = np.asarray(uids, np.int64).ravel()
        if uids.size == 0:
            return
        embs = np.asarray(fine_embs, np.float32).reshape(len(uids),
                                                         self.embed_dim)
        packed, scales = self._quantize_rows(embs)
        with self._lock:
            rows = self._rows_of_locked(uids)
            self._packed[rows] = packed
            self._scales[rows] = scales
            self._meta["fine"][rows] = True
            self._dirty[rows] = True
            self._any_dirty = True
            self._mark_bank_dirty_locked(rows)
            if self._ivf is not None:  # content changed -> cluster may too
                self._ivf.assign_rows(rows, embs, self._n)
            for u in uids.tolist():
                self._act_cache.pop(u, None)  # §3.4: storage freed once refined

    def delete(self, uid: int) -> None:
        self.delete_batch([uid])

    def delete_batch(self, uids: Sequence[int]) -> None:
        """Remove uids, keeping the slab dense: each deleted row is filled by
        swapping the current last row down (rows never leave holes, so the
        scan paths stay a contiguous [0, n) range). The moved row is marked
        dirty in both bitmaps — the dense cache requantizes it on the next
        refresh (copy-on-write if a snapshot escaped) and the device bank
        re-scatters it on the next epoch; the vacated tail rows are masked
        everywhere by the shrunken ``n``. Raises KeyError (before mutating
        anything) if any uid is absent."""
        uids = list(dict.fromkeys(int(u) for u in np.asarray(uids,
                                                             np.int64).ravel()))
        if not uids:
            return
        with self._lock:
            self._rows_of_locked(np.asarray(uids, np.int64))  # validate all
            for u in uids:
                row = self._uid_to_row.pop(u)
                self._act_cache.pop(u, None)
                last = self._n - 1
                if row != last:
                    self._packed[row] = self._packed[last]
                    self._scales[row] = self._scales[last]
                    self._meta[row] = self._meta[last]
                    self._uid_to_row[int(self._meta["uid"][row])] = row
                    self._dirty[row] = True
                    self._any_dirty = True
                    self._mark_bank_dirty_locked(np.array([row], np.int64))
                # the vacated tail slot must not leak into the next refresh
                # epoch (it is out of range for the shrunken n)
                self._dirty[last] = False
                self._unmark_bank_dirty_locked(last)
                self._n = last
                if self._ivf is not None:  # assignment swaps with the row
                    self._ivf.on_delete(row, last)

    # -- index ---------------------------------------------------------------

    def _rows_of_locked(self, uids: np.ndarray) -> np.ndarray:
        try:
            return np.fromiter((self._uid_to_row[int(u)] for u in uids),
                               np.int64, len(uids))
        except KeyError as e:
            raise KeyError(f"uid {e.args[0]} not in store") from None

    def rows_of(self, uids) -> np.ndarray:
        with self._lock:
            return self._rows_of_locked(np.asarray(uids, np.int64).ravel())

    def contains(self, uids) -> np.ndarray:
        """(len(uids),) bool mask of uids currently in the store. Retrieval
        uses it to drop candidates that were deleted after the scan that
        surfaced them — inherent to stale-serving under the async bank
        policy (a lagging snapshot can name uids that no longer exist),
        and a narrow race even on the exact paths."""
        uids = np.asarray(uids, np.int64).ravel()
        with self._lock:
            idx = self._uid_to_row
            return np.fromiter((int(u) in idx for u in uids), np.bool_,
                               len(uids))

    def row_of(self, uid: int) -> int:
        with self._lock:
            return self._uid_to_row[int(uid)]

    # seed-compat alias (the O(N) scan is gone; this is the hash index)
    def _index_of(self, uid: int) -> int:
        try:
            return self.row_of(uid)
        except KeyError:
            raise KeyError(uid)

    def __len__(self) -> int:
        return self._n

    def uids(self) -> np.ndarray:
        with self._lock:
            return self._meta["uid"][:self._n].copy()

    def is_fine(self, uids) -> np.ndarray:
        with self._lock:
            return self._meta["fine"][self._rows_of_locked(
                np.asarray(uids, np.int64).ravel())].copy()

    @property
    def n_fine(self) -> int:
        with self._lock:
            return int(self._meta["fine"][:self._n].sum())

    @property
    def entries(self) -> List[StoreEntry]:
        """Back-compat materialized row views (O(N); prefer the vectorized
        accessors — mutating the returned objects does NOT write back)."""
        with self._lock:
            m = self._meta[:self._n]
            return [StoreEntry(int(r["uid"]), int(r["exit_idx"]),
                               int(r["exit_layer"]),
                               self._modalities[int(r["modality_id"])],
                               bool(r["fine"])) for r in m]

    # -- access --------------------------------------------------------------

    def _refresh_dense_locked(self) -> None:
        """Dequantize only rows touched since the last refresh. If a view of
        the buffer escaped to a reader and an upgrade dirtied one of its rows,
        copy-on-write first so in-flight scans keep an internally consistent
        (stale-but-whole) snapshot instead of seeing torn rows."""
        if not self._any_dirty:
            return
        rows = np.nonzero(self._dirty[:self._n])[0]
        if rows.size:
            if self._escaped_n and (rows < self._escaped_n).any():
                self._dense = self._dense.copy()
                self._escaped_n = 0
            if self.store_int4:
                self._dense[rows] = spans.to_host(dequantize_int4(
                    spans.to_device(self._packed[rows]),
                    spans.to_device(self._scales[rows])))
            else:
                self._dense[rows] = self._packed[rows]
        self._dirty[:self._n] = False
        self._any_dirty = False

    def dense_matrix(self) -> np.ndarray:
        """(N, E) fp32 search matrix (incrementally-maintained cache).

        Returns a read-only snapshot view: later mutations land in a fresh or
        copied-on-write buffer, so the returned array stays internally
        consistent but goes stale. Use ``search`` / ``search_batch`` /
        ``get_embeddings`` for queries."""
        with self._lock:
            self._refresh_dense_locked()
            self._escaped_n = max(self._escaped_n, self._n)
            v = self._dense[:self._n]
            v.setflags(write=False)
            return v

    def get_embeddings(self, uids) -> np.ndarray:
        """(len(uids), E) fp32 dequantized rows — a lock-consistent copy."""
        uids = np.asarray(uids, np.int64).ravel()
        with self._lock:
            if uids.size == 0:
                return np.zeros((0, self.embed_dim), np.float32)
            self._refresh_dense_locked()
            return self._dense[self._rows_of_locked(uids)].copy()

    def cached_activation(self, uid: int) -> Optional[Tuple[np.ndarray, int]]:
        """Dequantized cached hidden state (h, exit_layer) or None."""
        out = self.cached_activations([uid])
        return out.get(int(uid))

    @spans.spanned("store.cached_activations")
    def cached_activations(self, uids) -> Dict[int, Tuple[np.ndarray, int]]:
        """Batched dequant of cached activations: one jnp call per distinct
        activation shape instead of one per uid. Returns {uid: (h, layer)}."""
        with self._lock:
            items = [(int(u), self._act_cache[int(u)]) for u in uids
                     if int(u) in self._act_cache]
        by_shape: Dict[Tuple[int, ...], List[Tuple[int, np.ndarray, np.ndarray, int]]] = {}
        for u, (p, s, shape, layer) in items:
            by_shape.setdefault(shape, []).append((u, p, s, layer))
        out: Dict[int, Tuple[np.ndarray, int]] = {}
        for shape, group in by_shape.items():
            packed = np.stack([g[1] for g in group])
            scales = np.stack([g[2] for g in group])
            hs = spans.to_host(dequantize_int4(spans.to_device(packed),
                                               spans.to_device(scales)))
            for (u, _, _, layer), h in zip(group, hs):
                out[u] = (h.reshape(shape), layer)
        return out

    def has_cached(self, uid: int) -> bool:
        with self._lock:
            return int(uid) in self._act_cache

    # -- device bank ---------------------------------------------------------

    def _mark_bank_dirty_locked(self, rows: np.ndarray) -> None:
        """Record freshly dirtied bank rows and keep the bounded-staleness
        counters exact: ``_bank_pending_rows`` counts DISTINCT dirty rows,
        ``_bank_first_dirty_t`` timestamps the oldest unpublished write.
        Wakes the async refresher, if any."""
        rows = np.unique(rows)  # a batch may hit one row twice (dup uids)
        fresh = int(np.count_nonzero(~self._bank_dirty[rows]))
        self._bank_dirty[rows] = True
        self._any_bank_dirty = True
        if fresh:
            self._bank_pending_rows += fresh
            if self._bank_first_dirty_t is None:
                self._bank_first_dirty_t = time.monotonic()
        ref = self._bank_refresher
        if ref is not None:
            ref.notify()

    def _unmark_bank_dirty_locked(self, row: int) -> None:
        if self._bank_dirty[row]:
            self._bank_dirty[row] = False
            self._bank_pending_rows -= 1
            if self._bank_pending_rows == 0:
                # nothing pending -> the "oldest unpublished write" stamp
                # must reset, or the next write inherits an ancient age and
                # the max_lag_ms policy spuriously fresh-blocks
                self._bank_first_dirty_t = None

    def _take_bank_dirty_locked(self) -> np.ndarray:
        """Consume the dirty slice for one refresh epoch: rows dirtied AFTER
        this call belong to the next epoch (they re-set their bit), so a
        concurrent writer is either fully in this epoch or fully in a later
        one — never half-included. Resets the staleness counters."""
        if self._any_bank_dirty:  # steady-state queries skip the O(N) scan
            rows = np.nonzero(self._bank_dirty[:self._n])[0]
            self._bank_dirty[:self._n] = False
            self._any_bank_dirty = False
        else:
            rows = np.zeros((0,), np.int64)
        self._bank_pending_rows = 0
        self._bank_first_dirty_t = None
        return rows

    def _requeue_bank_rows(self, rows: np.ndarray) -> None:
        """Put a consumed dirty slice back (a refresh epoch failed after its
        begin point): the rows must land in a later epoch, not vanish."""
        with self._lock:
            live = np.asarray(rows, np.int64)
            live = live[live < self._n]
            if live.size:
                self._mark_bank_dirty_locked(live)

    def attach_device_bank(self, devices=None, *, impl: str = "auto",
                           block_n: int = 4096):
        """Create (or replace) the device-resident searchable bank. ``devices``
        defaults to all of ``jax.devices()`` — rows are sharded across them
        when there is more than one. Existing rows are marked for upload on
        the next sync (the warm-up transfer); after that only dirty rows
        travel. Returns the bank (see ``repro.core.device_bank``)."""
        from repro.core.device_bank import DeviceBank
        with self._lock:
            self._bank = DeviceBank(self.embed_dim,
                                    store_int4=self.store_int4,
                                    devices=devices, impl=impl,
                                    block_n=block_n)
            if self._n:
                self._mark_bank_dirty_locked(np.arange(self._n))
            return self._bank

    @property
    def device_bank(self):
        """The attached DeviceBank, or None."""
        return self._bank

    @property
    def bank_refresher(self):
        """The async RefreshScheduler, or None in sync mode."""
        return self._bank_refresher

    def set_bank_refresh(self, mode: str = "sync", *,
                         max_lag_rows: Optional[int] = None,
                         max_lag_ms: Optional[float] = None,
                         thread: bool = True, **scheduler_kw):
        """Choose the device-bank refresh policy.

        ``"sync"`` (default): every ``search_batch(impl='device')`` brings
        the bank exactly up to date under the store lock before scanning —
        PR 2 semantics; tears down any async scheduler (draining its
        pending rows into one last flip).

        ``"async"``: refresh runs as double-buffered epochs OUTSIDE the
        lock (``repro.core.bank_refresh``), by a background thread unless
        ``thread=False`` (then the caller steps the returned scheduler).
        Queries serve the published — possibly lagging — snapshot while
        dirt stays within ``max_lag_rows`` / ``max_lag_ms`` (None =
        unbounded, 0 = fresh-blocking) and block for a refresh otherwise.
        Returns the scheduler (async) or None (sync)."""
        from repro.core.bank_refresh import RefreshScheduler
        if mode not in ("sync", "async"):
            raise ValueError(mode)
        old = self._bank_refresher
        if old is not None:
            # drain while queries still route through the scheduler: if the
            # refresher were unhooked first, a query could enter the sync
            # path and race the drain's epoch (two unserialized refresh
            # drivers). The bank's refresh_lock closes the remaining
            # unhook-vs-in-flight-epoch window.
            old.stop(drain=True)
            self._bank_refresher = None
        if mode == "sync":
            return None
        ref = RefreshScheduler(self, max_lag_rows=max_lag_rows,
                               max_lag_ms=max_lag_ms, thread=thread,
                               **scheduler_kw)
        self._bank_refresher = ref
        return ref

    def kick_bank_refresh(self) -> bool:
        """Hint that now is a good moment to refresh (e.g. right after an
        embedding drain, so the scatter hides behind host work instead of
        landing on the first query). No-op in sync mode."""
        ref = self._bank_refresher
        if ref is None:
            return False
        ref.notify()
        return True

    def _sync_bank_locked(self):
        """In-lock refresh (sync mode): scatter only the rows dirtied since
        the last refresh (the bank grows device-side in lockstep with host
        slab doublings) and publish. Returns (bank, snapshot) — the
        consistency point the scan is pinned to (a concurrent later
        refresh, or a bank re-attach, must not retarget it)."""
        if self._bank is None:
            self.attach_device_bank()
        bank = self._bank
        rows = self._take_bank_dirty_locked()
        # the scatter is dispatched, not waited for: the scan that follows
        # waits for it
        with spans.span("bank.sync_dispatch"):
            rows0 = bank.h2d_rows
            snap = bank.sync(self._packed, self._scales, self._n, rows,
                             self._meta["uid"][:self._n].copy())
            spans.count("bank_rows", bank.h2d_rows - rows0)
        return bank, snap

    # -- IVF coarse-filter index ---------------------------------------------

    def attach_ivf(self, *, n_clusters: int = 64, nprobe: int = 8,
                   min_rows: int = 32_768, seed: int = 0, **kw):
        """Create (or replace) the online IVF coarse-filter index
        (``repro.index.ivf``). Existing rows seed the centroids and are
        assigned immediately when there are enough of them; otherwise
        training starts from the insert stream. ``search_batch`` gains
        ``impl='ivf'`` (pruned scan over the device bank), and ``'auto'``
        cuts over to it once the store holds ``min_rows`` rows. Requires
        the int4 slab layout (the pruned kernel is the fused int4 scan).
        Returns the index."""
        from repro.index.ivf import IVFIndex
        assert self.store_int4, "IVF pruned search needs store_int4=True"
        with self._lock:
            idx = IVFIndex(self.embed_dim, n_clusters=n_clusters,
                           nprobe=nprobe, min_rows=min_rows, seed=seed, **kw)
            idx.ensure_capacity(self._cap)
            if self._n:
                self._refresh_dense_locked()
                if self._n >= n_clusters:
                    idx.init_from(self._dense[:self._n])
                else:  # too few rows to seed: buffer them as training data
                    idx.observe(self._dense[:self._n])
                idx.assign_rows(np.arange(self._n), self._dense[:self._n],
                                self._n)
            self._ivf = idx
            return idx

    @property
    def ivf_index(self):
        """The attached IVFIndex, or None."""
        return self._ivf

    def ivf_recluster_begin(self):
        """Phase 1 of a re-cluster job (store-side driver): take the index's
        recluster lock (non-blocking — one job in flight across the sync
        search path and the async refresh thread), check the trigger, and
        snapshot under the store lock. Returns a ``ReclusterJob`` or None
        (no index / untrained / no trigger / job already running). The
        caller MUST finish with ``ivf_recluster_commit`` or
        ``ivf_recluster_abort``."""
        idx = self._ivf
        if idx is None or not idx.recluster_lock.acquire(blocking=False):
            return None
        try:
            with self._lock:
                if not idx.trained:
                    # late init: the index was attached before enough rows
                    # existed and insert traffic never filled the buffer.
                    # Seed + train on a BOUNDED subsample only — begin
                    # runs under the store lock and a full-corpus
                    # init_from would stall every writer and query for
                    # O(n*C*E); the unassigned-rows trigger then fires
                    # THIS job, whose unlocked compute phase assigns and
                    # Lloyd-refines over the full corpus anyway.
                    if self._n < idx.n_clusters:
                        idx.recluster_lock.release()
                        return None
                    self._refresh_dense_locked()
                    m = min(self._n,
                            max(idx.n_clusters + 1,
                                int(idx.n_clusters * idx.init_oversample)))
                    sel = (np.arange(self._n) if m == self._n else
                           idx._rng.choice(self._n, m, replace=False))
                    idx.init_from(self._dense[sel])
                if not idx.needs_recluster():
                    idx.recluster_lock.release()
                    return None
                # COW view: rows < n stay stable while compute runs unlocked
                self._refresh_dense_locked()
                self._escaped_n = max(self._escaped_n, self._n)
                return idx.begin_recluster(self._dense)
        except BaseException:
            idx.recluster_lock.release()
            raise

    def ivf_recluster_commit(self, job) -> None:
        """Phase 3: apply the computed assignment under the store lock and
        release the job lock. Targets the index the JOB belongs to
        (``job.owner``), not ``self._ivf`` — a concurrent ``attach_ivf``
        may have swapped the attached index mid-job, and commit must not
        touch the replacement (whose recluster_lock it does not hold)."""
        idx = job.owner
        try:
            with self._lock:
                if idx is self._ivf:
                    idx.commit_recluster(job, self._n)
                else:  # index was replaced mid-job: result is obsolete
                    idx.abort_recluster()
        finally:
            idx.recluster_lock.release()

    def ivf_recluster_abort(self, job) -> None:
        idx = job.owner
        try:
            with self._lock:
                idx.abort_recluster()
        finally:
            idx.recluster_lock.release()

    def ivf_maybe_recluster(self) -> bool:
        """Run one full re-cluster job if the index wants one (begin ->
        unlocked O(n·C) argmin -> commit). The async refresh thread calls
        this after each epoch so re-assignment piggybacks on refresh and
        never blocks serving; in sync mode the ``impl='ivf'`` query path
        calls it inline (sync queries already pay refresh inline)."""
        from repro.index.ivf import IVFIndex
        job = self.ivf_recluster_begin()
        if job is None:
            return False
        try:
            IVFIndex.compute_assignments(job)  # no locks held
        except BaseException:
            self.ivf_recluster_abort(job)
            raise
        self.ivf_recluster_commit(job)
        return True

    # -- search --------------------------------------------------------------

    def _search_snapshot(self) -> Tuple[np.ndarray, int, np.ndarray]:
        """(full dense slab, row count, uid copy) taken under the lock. The
        scan itself runs OUTSIDE the lock so queries don't serialize inserts.
        The snapshot is consistent for rows < n: growth reallocates into a
        fresh buffer, and a later upgrade overlapping an escaped view
        triggers copy-on-write in ``_refresh_dense_locked`` — a concurrent
        reader sees stale-but-whole rows, never torn ones. (Rows >= n are
        masked by every consumer, so concurrent appends there are benign.)"""
        with self._lock:
            self._refresh_dense_locked()
            self._escaped_n = max(self._escaped_n, self._n)
            return (self._dense, self._n,
                    self._meta["uid"][:self._n].copy())

    def search(self, query: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """Top-k by inner product (numpy reference path): (uids, scores)."""
        q = np.asarray(query, np.float32)
        if self._n == 0:
            return np.zeros((0,), np.int64), np.zeros((0,), np.float32)
        slab, n, uids = self._search_snapshot()
        scores = slab[:n] @ q
        k = min(k, n)
        idx = np.argpartition(-scores, k - 1)[:k]
        idx = idx[np.argsort(-scores[idx])]
        return uids[idx], scores[idx]

    @spans.spanned("store.search_batch")
    def search_batch(self, queries: np.ndarray, k: int, *, impl: str = "auto",
                     freshness: Optional[str] = None,
                     nprobe: Optional[int] = None,
                     **kw) -> Tuple[np.ndarray, np.ndarray]:
        """Fused batched top-k over the whole store: queries (Q, E) ->
        (uids (Q, k), scores (Q, k)), both sorted by descending score.

        ``impl='auto'`` picks the device-resident bank on accelerators
        (``'device'``: int4 slab stays on device, incremental dirty-row
        refresh, fused dequant scan — zero slab re-upload per query) and the
        numpy matmul+argpartition host path on CPU (where the kernel only
        runs in interpret mode, ~10x slower — see BENCH_store_scale.json;
        the device path works on CPU too, it just loses to BLAS).
        ``impl='device'``/``'pallas'``/``'xla'``/``'numpy'`` force a
        backend; the latter two re-upload the fp32 slab every call. Scores
        are raw inner products (normalize=False) to match ``search``.

        ``impl='ivf'`` is the coarse-filtered pruned path (requires
        ``attach_ivf``): top-``nprobe`` centroids per query, then the
        gathered fused int4 scan over only those clusters' rows on the
        device bank — work scales with the probed posting mass, not the
        store size. On accelerators ``'auto'`` cuts over to it once the
        store holds the index's ``min_rows`` (on CPU auto keeps numpy:
        BLAS beats the pruned scan at every measured size — see
        ``_resolve_auto_impl``). Approximate: a query returns the exact
        top-k *of the probed clusters*; slots past a query's live
        candidate count hold uid -1 / score -1e30. ``nprobe`` overrides
        the index default for this call (ignored by every other impl).

        ``freshness`` applies to the device and ivf paths under an async
        refresh policy (``set_bank_refresh("async", ...)``): None obeys
        the configured staleness bound, ``"fresh"`` blocks for a refresh,
        ``"stale"`` serves the published generation as-is. In sync mode
        (default) every device query is exact and ``freshness`` is
        ignored."""
        queries = np.asarray(queries, np.float32).reshape(-1, self.embed_dim)
        nq = len(queries)
        if self._n == 0 or nq == 0:
            return (np.zeros((nq, 0), np.int64),
                    np.zeros((nq, 0), np.float32))
        if impl == "auto":
            impl = self._resolve_auto_impl()
        if impl == "ivf":
            return self._search_ivf(queries, k, freshness=freshness,
                                    nprobe=nprobe, **kw)
        if impl == "device":
            ref = self._bank_refresher
            if ref is not None:
                # async: no store lock on the query path at all — the
                # scheduler hands back a published generation (refreshing
                # first only when the policy demands it)
                bank, snap, _ = self._async_bank_coherent(ref, freshness)
            else:
                with self._lock:
                    bank, snap = self._sync_bank_locked()
            if snap.n == 0:
                return (np.zeros((nq, 0), np.int64),
                        np.zeros((nq, 0), np.float32))
            # the scan runs outside the lock, pinned to the refresh-point
            # bank AND snapshot (immutable arrays; a racing refresh or
            # re-attach publishes/installs the NEXT one), so row indices
            # stay aligned with the snapshot's uid copy
            idx, top_s = bank.search(queries, min(k, snap.n), state=snap,
                                     **kw)
            return snap.uids[idx], top_s
        slab, n, uids = self._search_snapshot()
        k = min(k, n)
        if impl == "numpy":
            scores = queries @ slab[:n].T                       # (Q, N)
            idx = np.argpartition(-scores, k - 1, axis=1)[:, :k]
            part = np.take_along_axis(scores, idx, axis=1)
            order = np.argsort(-part, axis=1)
            idx = np.take_along_axis(idx, order, axis=1)
            top_s = np.take_along_axis(part, order, axis=1)
        else:
            from repro.kernels.retrieval_topk.ops import retrieval_topk
            # hand the kernel the whole capacity slab + a runtime row count:
            # the traced bank shape then changes only on slab doublings
            # (O(log N) compiles), not once per store size
            self.upload_bytes += int(slab.nbytes)  # full fp32 slab, per call
            self.upload_calls += 1
            s, i = retrieval_topk(spans.to_device(queries),
                                  spans.to_device(slab), k, normalize=False,
                                  impl=impl, n_valid=n, **kw)
            idx = spans.to_host(i).astype(np.int64, copy=False)
            top_s = spans.to_host(s).astype(np.float32, copy=False)
        return uids[idx], top_s

    def _resolve_auto_impl(self) -> str:
        """``impl='auto'`` resolution (factored for direct testing — the
        accelerator branches can't execute on a CPU-only box).

        CPU: the BLAS matmul beats every kernel path including the pruned
        scan (BENCH_store_scale: qps_numpy > qps_ivf at all sizes — the
        gather+scan overhead outruns the FLOP savings when BLAS is this
        cheap), so auto stays on numpy; ``impl='ivf'`` remains available
        explicitly. Accelerators: the IVF pruned path once the store holds
        the index's ``min_rows`` (>= 3x the exhaustive device scan there,
        asserted in the bench) — sharded banks included, now that the
        pruned scan shard-routes the candidate set instead of falling back
        to the exhaustive sharded scan."""
        if jax.default_backend() == "cpu":
            return "numpy"
        if self._ivf is not None and self._ivf.searchable(self._n):
            return "ivf"
        return "device"

    def _async_bank_coherent(self, ref, freshness: Optional[str],
                             cand_fn=None):
        """Resolve a coherent (bank, snapshot[, candidates]) triple on the
        async query path WITHOUT holding the store lock across the
        (possibly blocking) refresh: the snapshot must belong to the SAME
        bank object the scan will run on — a concurrent
        ``attach_device_bank`` swaps ``self._bank`` for a fresh object, and
        pairing the old bank's snapshot with the new bank (or one bank's
        snapshot with another's posting-list candidates) would scan
        mismatched row spaces. Banks are never reused, so observing
        ``self._bank is bank`` under the lock AFTER taking the snapshot
        proves no swap happened in between; ``cand_fn`` (candidate
        building) runs inside that same lock hold. A re-attach storm
        (bounded retries exhausted) falls back to the fully-coherent
        in-lock sync refresh — the bank's refresh_lock serializes it
        against any in-flight scheduler epoch."""
        for _ in range(8):
            bank = self._bank
            snap = ref.snapshot_for_query(freshness)
            with self._lock:
                if bank is not None and self._bank is bank:
                    return bank, snap, (None if cand_fn is None
                                        else cand_fn())
        with self._lock:
            bank, snap = self._sync_bank_locked()
            return bank, snap, (None if cand_fn is None else cand_fn())

    def _search_ivf(self, queries: np.ndarray, k: int, *,
                    freshness: Optional[str], nprobe: Optional[int],
                    strategy: str = "union",
                    **kw) -> Tuple[np.ndarray, np.ndarray]:
        """IVF pruned scan over the device bank (see ``search_batch``).
        Candidate rows come from the CURRENT posting lists while the scan
        runs against ONE published snapshot: in sync mode the two are taken
        under the same lock hold, so they agree exactly; under the async
        policy the bank/snapshot/candidate pairing is resolved by
        ``_async_bank_coherent`` (candidates build in the same lock hold
        that validates the pairing) and the postings may run ahead of a
        stale generation — candidate ids past ``snap.n`` are
        masked/filtered, rows deleted since the flip simply drop out, both
        within the configured staleness semantics (re-scoring in retrieval
        rounds 2/3 is against live rows either way).

        ``strategy='union'`` (default) gathers the union of every query's
        probed clusters ONCE and feeds the batch through the standard
        fused scan — a query may score a batchmate's candidates, which is
        strictly a recall bonus, and the shared matmul amortizes like the
        exhaustive path. ``'gathered'`` scans each query's own (Q, L)
        candidate block via the per-query gathered kernel (the
        TPU-targeted variant; no cross-query candidates). On a row-sharded
        bank both strategies shard-route: the union partitions by shard
        ownership (each shard scans only its local candidate slice), the
        gathered path masks per shard, and the per-shard partial top-k
        merge through ``topk_allgather_merge``."""
        idx_obj = self._ivf
        if idx_obj is None:
            raise ValueError("impl='ivf' requires attach_ivf() first")
        if strategy not in ("union", "gathered"):
            raise ValueError(f"ivf strategy={strategy!r}")
        nq = len(queries)
        ref = self._bank_refresher
        if ref is None:
            # sync mode pays maintenance inline on the query path (exactly
            # like the in-lock bank refresh); async leaves it to the
            # refresh thread, which piggybacks re-clustering on epochs
            self.ivf_maybe_recluster()
            with self._lock:
                bank, snap = self._sync_bank_locked()
                cand = self._ivf_candidates_locked(queries, k, nprobe,
                                                   strategy)
        else:
            bank, snap, cand = self._async_bank_coherent(
                ref, freshness,
                lambda: self._ivf_candidates_locked(queries, k, nprobe,
                                                    strategy))
        if snap.n == 0:
            return (np.zeros((nq, 0), np.int64),
                    np.zeros((nq, 0), np.float32))
        k = min(k, snap.n)
        if strategy == "union" and cand is not None:
            cand = cand[cand < snap.n]  # postings ahead of a stale snap
            if cand.size == 0:
                cand = None
        if cand is None:
            # untrained index (too few rows yet) or empty probe set:
            # serve exhaustively — correct, just not pruned
            self.ivf_fallbacks += 1
            ridx, top_s = bank.search(queries, k, state=snap, **kw)
            return snap.uids[ridx], top_s
        if strategy == "union":
            k2 = min(k, int(cand.size))
            gids, top_s = bank.search_rows(queries, cand, k2, state=snap,
                                           **kw)
            # a sharded merge can surface sentinel slots (a shard short of
            # candidates); map them to uid -1 like the gathered path
            live = top_s > -5e29
            uids = np.where(live, snap.uids[np.clip(gids, 0, snap.n - 1)],
                            -1)
            if k2 < k:  # union smaller than k: pad with the sentinel
                uids = np.pad(uids, ((0, 0), (0, k - k2)),
                              constant_values=-1)
                top_s = np.pad(top_s, ((0, 0), (0, k - k2)),
                               constant_values=-1e30)
            return uids, top_s
        ridx, top_s = bank.search_gathered(queries, cand, k, state=snap,
                                           **kw)
        live = top_s > -5e29  # kernel sentinel for dead/padded slots
        uids = np.where(live, snap.uids[np.clip(ridx, 0, snap.n - 1)], -1)
        return uids, top_s

    def _ivf_candidates_locked(self, queries, k, nprobe, strategy):
        idx_obj = self._ivf
        if not idx_obj.trained:
            return None
        if strategy == "union":
            return idx_obj.candidate_union(queries, nprobe=nprobe)
        return idx_obj.candidate_rows(queries, k, nprobe=nprobe)

    # -- accounting ----------------------------------------------------------

    def storage_bytes(self) -> Dict[str, int]:
        with self._lock:
            emb = int(self._packed[:self._n].nbytes +
                      self._scales[:self._n].nbytes)
            act = sum(p.nbytes + s.nbytes
                      for p, s, _, _ in self._act_cache.values())
            return {"embeddings": emb, "act_cache": act, "total": emb + act,
                    "per_item": emb // max(self._n, 1)}

    def exit_histogram(self, n_exits: int) -> np.ndarray:
        with self._lock:
            return np.bincount(self._meta["exit_idx"][:self._n],
                               minlength=n_exits).astype(np.int64)[:n_exits]
