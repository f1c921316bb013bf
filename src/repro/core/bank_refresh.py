"""Async double-buffered DeviceBank refresh scheduler.

PR 2's device bank synced *inside* the store's mutation lock: correct, but
every post-mutation query paid the dirty-row scatter dispatch on its own
critical path, and scans serialized behind writers for the sync's duration.
This module moves the refresh out of the lock into an explicit three-phase
epoch so scans and refreshes overlap (the ROADMAP "double-buffered banks /
async device_put" item):

  1. ``begin_epoch`` — under the store lock, but O(dirty) cheap: slice the
     dirty bitmap (clear it — rows dirtied afterwards belong to the NEXT
     epoch, the epoch-sliced handoff that keeps a racing writer from being
     half-included), copy just those rows' packed bytes + scales, and
     snapshot (n, uids). Everything the device work needs is now immutable.
  2. ``apply`` — outside any lock: device-side capacity growth + the
     dirty-row scatter into the SHADOW snapshot (``DeviceBank.apply_rows``;
     async dispatch, donated buffers when the shadow is private). Published
     state untouched; in-flight scans proceed against it.
  3. ``flip`` — one atomic attribute write publishes the shadow with a new
     generation. All-or-nothing: no scan can observe a half-applied epoch.

``refresh_once`` runs the three phases back to back (serialized by an epoch
lock so a blocking query and the background thread can't interleave
epochs). The background thread coalesces mutation bursts into single epochs
(debounced wake) and enforces the bounded-staleness knobs:

  * ``max_lag_rows`` — serve-stale is allowed while fewer than this many
    distinct rows are dirty-but-unpublished; ``0`` means every query
    refreshes first (fresh-blocking, PR 2 semantics minus the lock), and
    ``None`` means unbounded.
  * ``max_lag_ms``  — ... and while the oldest unpublished write is younger
    than this; same ``0`` / ``None`` meanings.

``snapshot_for_query`` is the store's entry point: it applies the policy
(or an explicit per-query ``freshness`` override: ``"fresh"`` blocks for a
refresh, ``"stale"`` serves the published generation as-is) and returns the
snapshot to scan. The deterministic concurrency harness
(``tests/harness_concurrency.py``) drives ``begin_epoch``/``apply``/``flip``
directly as separate schedule steps, which is why they are public.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Optional, Tuple

import numpy as np

from repro.core.device_bank import BankSnapshot


@dataclasses.dataclass
class RefreshEpoch:
    """One epoch's immutable handoff: the dirty-row payload copied under the
    store lock at begin, plus the row count / uid snapshot of that instant.
    ``bank`` pins the DeviceBank the epoch was begun against — apply/flip
    must target IT, not ``store._bank``: a concurrent re-attach swaps the
    store's bank for a fresh (empty) object, and scattering this epoch's
    partial dirty slice into the replacement would publish a bank whose
    un-scattered rows are zeros (the re-attach re-marks every row dirty,
    so the NEXT epoch uploads the replacement in full; this one's flip
    lands on the retired bank, where it is harmless)."""
    rows: np.ndarray                       # host row indices to scatter
    vals: np.ndarray                       # packed payload copy, (m, E//2)
    scs: np.ndarray                        # scales copy, (m, 1)
    n: int                                 # store row count at begin
    uids: np.ndarray                       # (n,) uid snapshot at begin
    host_cap: int                          # host slab capacity at begin
    bank: object = None                    # DeviceBank pinned at begin
    snapshot: Optional[BankSnapshot] = None  # shadow, filled by apply()


class RefreshScheduler:
    """Drives async DeviceBank refresh for one store (one epoch in flight at
    a time). Construct via ``EmbeddingStore.set_bank_refresh("async", ...)``;
    ``thread=True`` runs epochs on a daemon thread woken by store mutations,
    ``thread=False`` leaves stepping to the caller (tests / manual)."""

    def __init__(self, store, *, max_lag_rows: Optional[int] = None,
                 max_lag_ms: Optional[float] = None, thread: bool = True,
                 debounce_ms: float = 2.0, idle_ms: float = 50.0):
        self.store = store
        self.max_lag_rows = max_lag_rows
        self.max_lag_ms = max_lag_ms
        self.mode = "async"
        self._epoch_lock = threading.Lock()   # serializes whole epochs
        self._wake = threading.Event()
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        self._debounce_s = debounce_ms / 1e3
        self._idle_s = idle_ms / 1e3
        # observability (reads are approximate under concurrency)
        self.n_epochs = 0
        self.n_blocking = 0       # queries that waited for a refresh
        self.n_stale_served = 0   # queries served a lagging snapshot
        # the exception that stopped the background thread, re-raised by
        # every later query
        self.failure: Optional[BaseException] = None
        if thread:
            self.start()

    # -- epoch phases (the harness calls these as separate schedule steps) --

    def begin_epoch(self) -> Optional[RefreshEpoch]:
        """Phase 1, under the store lock: take the dirty slice + payload
        copies. Returns None when the published snapshot is already exact
        (no dirty rows and the row count matches)."""
        st = self.store
        with st._lock:
            if st._bank is None:
                st.attach_device_bank()
            bank = st._bank
            rows = st._take_bank_dirty_locked()
            pub = bank.published
            if rows.size == 0 and pub is not None and pub.n == st._n:
                return None
            return RefreshEpoch(
                rows=rows, vals=st._packed[rows].copy(),
                scs=st._scales[rows].copy(), n=st._n,
                uids=st._meta["uid"][:st._n].copy(),
                host_cap=st._packed.shape[0], bank=bank)

    def apply(self, epoch: RefreshEpoch) -> BankSnapshot:
        """Phase 2, no locks: build the shadow snapshot (grow + scatter).
        If the epoch grew device capacity, pre-warm the search executable
        against the shadow BEFORE it is published — a capacity change
        forces a retrace + compile worth 10-20x a steady scan, which the
        sync path pays inline on the first post-growth query; here it
        happens off the query path while scans keep hitting the old
        generation's cached executable. Targets the epoch's OWN bank (see
        ``RefreshEpoch.bank``), which a concurrent re-attach may already
        have retired."""
        bank = epoch.bank
        old_cap = bank.capacity
        epoch.snapshot = bank.apply_rows(
            epoch.host_cap, epoch.rows, epoch.vals, epoch.scs,
            epoch.n, epoch.uids)
        if bank.capacity != old_cap:
            bank.warm(epoch.snapshot)
        return epoch.snapshot

    def flip(self, epoch: RefreshEpoch) -> BankSnapshot:
        """Phase 3: atomically publish the shadow (onto the epoch's own
        bank — a no-op for serving if a re-attach retired it mid-epoch)."""
        self.n_epochs += 1
        return epoch.bank.publish(epoch.snapshot)

    def refresh_once(self) -> bool:
        """Run one full epoch (begin -> apply -> flip); False if clean.
        Serialized two ways: concurrent scheduler callers queue on the
        epoch lock (the winner's begin point covers every earlier write),
        and apply+flip additionally hold the BANK's refresh lock so an
        in-lock ``bank.sync`` from the sync query path (possible while the
        scheduler is being torn down) can never mint a generation
        concurrently with this epoch."""
        with self._epoch_lock:
            epoch = self.begin_epoch()
            if epoch is None:
                return False
            try:
                # the EPOCH's bank's refresh lock: serializes against an
                # in-lock bank.sync from the sync query path targeting the
                # same bank (a re-attached replacement has its own lock —
                # and its own full-dirty warm-up epoch coming)
                with epoch.bank.refresh_lock:
                    self.apply(epoch)
                    self.flip(epoch)
            except BaseException:
                # the dirty slice was consumed at begin — put it back so the
                # rows aren't silently dropped from every later epoch
                self.store._requeue_bank_rows(epoch.rows)
                raise
            return True

    # -- staleness policy ---------------------------------------------------

    def lag(self) -> Tuple[int, float]:
        """(dirty-but-unpublished row count, ms since the oldest of them)."""
        st = self.store
        with st._lock:
            rows = st._bank_pending_rows
            t0 = st._bank_first_dirty_t
        ms = 0.0 if (t0 is None or rows == 0) else \
            (time.monotonic() - t0) * 1e3
        return rows, ms

    def within_bound(self) -> bool:
        rows, ms = self.lag()
        if rows == 0:
            return True
        if self.max_lag_rows is not None and rows > self.max_lag_rows:
            return False
        if self.max_lag_ms is not None and ms > self.max_lag_ms:
            return False
        return True

    def snapshot_for_query(self, freshness: Optional[str] = None
                           ) -> BankSnapshot:
        """Resolve the snapshot a query should scan. ``freshness``:
        None -> the configured staleness bound decides; ``"fresh"`` ->
        always block for a refresh; ``"stale"`` -> serve the published
        generation without checking the bound (still refreshes when
        nothing was ever published)."""
        if freshness not in (None, "fresh", "stale"):
            raise ValueError(f"freshness={freshness!r}")
        if self.failure is not None:
            raise RuntimeError("background bank refresh failed") \
                from self.failure
        bank = self.store._bank
        snap = None if bank is None else bank.published
        if snap is not None and freshness == "stale":
            self.n_stale_served += 1
            return snap
        if snap is None or freshness == "fresh" or not self.within_bound():
            self.n_blocking += 1
            self.refresh_once()
            snap = self.store._bank.published
        else:
            self.n_stale_served += 1
        return snap

    # -- background thread --------------------------------------------------

    def notify(self) -> None:
        """Mutation hook: wake the background refresher (no-op w/o thread)."""
        self._wake.set()

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop = False
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bank-refresh")
        self._thread.start()

    def stop(self, drain: bool = True) -> None:
        """Stop the thread; ``drain`` publishes any remaining dirt first."""
        self._stop = True
        self._wake.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=30)
        if drain:
            self.refresh_once()

    def _run(self) -> None:
        while not self._stop:
            fired = self._wake.wait(timeout=self._idle_s)
            if self._stop:
                break
            if fired:
                self._wake.clear()
                # debounce: let a mutation burst coalesce into ONE epoch
                # (one scatter dispatch) instead of an epoch per add_batch
                time.sleep(self._debounce_s)
            try:
                self.refresh_once()
                # IVF re-clustering piggybacks on refresh epochs: the
                # O(n·C) re-assignment runs HERE (its compute phase holds
                # no locks at all), so serving never blocks on it — the
                # sync path, by contrast, pays it inline on a query.
                # Loop while jobs fire: codebook auto-growth converges on
                # ~sqrt(n) over SEVERAL bounded (<= 2x) steps, and each
                # should land now rather than one idle period apart
                while self.store.ivf_maybe_recluster() and not self._stop:
                    pass
            except Exception as e:
                # the epoch's dirt was requeued; stop here and let the next
                # query raise it rather than serve an ever-staler bank
                self.failure = e
                return
