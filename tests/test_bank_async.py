"""Async double-buffered DeviceBank refresh: deterministic interleaving
enumeration (tests/harness_concurrency.py), staleness policy, epoch-sliced
dirty handoff, failure requeue, and a real-thread smoke test.
"""
import threading
import time

import numpy as np
import pytest

from repro.core.store import EmbeddingStore
from tests.harness_concurrency import (ConcurrencyScenario, apply_mutation,
                                       enumerate_interleavings, make_script)


def _embs(n, e=32, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, e)).astype(np.float32)


# ---------------------------------------------------------------------------
# enumerated interleavings: every schedule bit-identical to the sync oracle
# ---------------------------------------------------------------------------


def test_enumerated_interleavings_match_sync_oracle():
    """2 writer steps x 1 refresh epoch (3 phases) x 2 scans = 210 distinct
    interleavings, each asserting: no torn generations (scan == oracle of
    ONE prefix, bit-identical), flip all-or-nothing, drain convergence."""
    scen = ConcurrencyScenario(freshness="stale")
    schedules = enumerate_interleavings({"W": 2, "R": 3, "S": 2})
    assert len(schedules) == 210
    total_stale = 0
    for sched in schedules:
        stats = scen.run_schedule(sched)
        assert stats["scans"] == 2
        total_stale += stats["stale_scans"]
    # sanity that the enumeration actually exercised lagging reads: in many
    # schedules a scan lands between a write and its flip
    assert total_stale > 50


def test_enumerated_interleavings_with_delete_and_policy_bound():
    """3 writer steps (incl. delete_batch) x 1 epoch x 1 policy scan, even
    140-schedule subsample: bounded staleness (max_lag_rows) must hold after
    every policy-driven scan, on top of the oracle equality."""
    scen = ConcurrencyScenario(freshness=None, max_lag_rows=4)
    schedules = enumerate_interleavings({"W": 3, "R": 3, "S": 1})
    assert len(schedules) == 140
    for sched in schedules:
        scen.run_schedule(sched)


def test_enumerated_interleavings_with_bank_reattach():
    """W/R/S schedules with an ``A`` (attach_device_bank re-attach) token:
    an epoch begun on the old bank must complete against IT
    (``RefreshEpoch.bank``) — scattering its partial dirty slice into the
    fresh bank would publish zeros for un-scattered rows — and the next
    epoch re-uploads the replacement in full; every scan still maps onto
    exactly one sync-oracle prefix (generations keyed per bank)."""
    scen = ConcurrencyScenario(freshness="stale")
    # 8!/(2!3!2!1!) = 1680 distinct schedules; even 140-schedule subsample
    schedules = enumerate_interleavings({"W": 2, "R": 3, "S": 2, "A": 1},
                                        stride=12)
    assert len(schedules) == 140
    for sched in schedules:
        stats = scen.run_schedule(sched)
        assert stats["scans"] == 2 and stats["attaches"] == 1


def test_interleaving_count_meets_spec():
    """The harness enumerates at least 50 distinct schedules (acceptance
    floor) and they are genuinely distinct."""
    schedules = enumerate_interleavings({"W": 2, "R": 3, "S": 2})
    assert len(set(schedules)) == len(schedules) >= 50


def test_enumerate_interleavings_subsampling():
    full = enumerate_interleavings({"A": 2, "B": 2})
    assert full == ["AABB", "ABAB", "ABBA", "BAAB", "BABA", "BBAA"]
    assert enumerate_interleavings({"A": 2, "B": 2}, stride=2) == \
        ["AABB", "ABBA", "BABA"]
    assert enumerate_interleavings({"A": 2, "B": 2}, limit=2) == \
        ["AABB", "ABAB"]


# ---------------------------------------------------------------------------
# staleness policy unit behavior
# ---------------------------------------------------------------------------


def _store_with_rows(n=60, E=32):
    st = EmbeddingStore(E, capacity=8)
    st.add_batch(np.arange(n), _embs(n, E), np.zeros(n), np.ones(n))
    return st


def test_stale_serving_within_row_bound():
    st = _store_with_rows()
    q = _embs(3, seed=5)
    ref = st.set_bank_refresh("async", max_lag_rows=8, thread=False)
    st.search_batch(q, 5, impl="device")            # publishes gen 1
    gen = st.device_bank.generation
    st.upgrade_batch([1, 2], _embs(2, seed=9))      # 2 dirty rows < bound
    st.search_batch(q, 5, impl="device")
    assert st.device_bank.generation == gen          # served stale
    assert ref.n_stale_served >= 1
    st.upgrade_batch(np.arange(10, 20), _embs(10, seed=10))  # 12 > bound
    st.search_batch(q, 5, impl="device")
    assert st.device_bank.generation > gen           # blocked + refreshed
    assert ref.lag() == (0, 0.0)


def test_fresh_and_stale_overrides():
    st = _store_with_rows()
    q = _embs(3, seed=5)
    ref = st.set_bank_refresh("async", max_lag_rows=None, thread=False)
    st.search_batch(q, 5, impl="device")
    gen = st.device_bank.generation
    st.upgrade_batch(np.arange(30), _embs(30, seed=11))
    # unbounded lag: default serves stale no matter how much dirt
    st.search_batch(q, 5, impl="device")
    assert st.device_bank.generation == gen
    # "stale" serves as-is, "fresh" always blocks for a refresh
    st.search_batch(q, 5, impl="device", freshness="stale")
    assert st.device_bank.generation == gen
    u, _ = st.search_batch(q, 5, impl="device", freshness="fresh")
    assert st.device_bank.generation > gen
    nu, _ = st.search_batch(q, 5, impl="numpy")
    for a, b in zip(u, nu):
        assert set(a.tolist()) == set(b.tolist())
    with pytest.raises(ValueError):
        ref.snapshot_for_query("fresh-ish")


def test_time_bound_blocks_old_writes():
    st = _store_with_rows()
    q = _embs(3, seed=5)
    st.set_bank_refresh("async", max_lag_ms=5.0, thread=False)
    st.search_batch(q, 5, impl="device")
    gen = st.device_bank.generation
    st.upgrade_batch([4], _embs(1, seed=12))
    time.sleep(0.02)                                 # older than the bound
    st.search_batch(q, 5, impl="device")
    assert st.device_bank.generation > gen


def test_sync_mode_unchanged_and_mode_switch_drains():
    st = _store_with_rows()
    q = _embs(3, seed=6)
    u_sync, s_sync = st.search_batch(q, 5, impl="device")  # sync default
    assert st.bank_refresher is None
    ref = st.set_bank_refresh("async", thread=False)
    st.upgrade_batch([7], _embs(1, seed=13))
    assert ref.lag()[0] == 1
    st.set_bank_refresh("sync")                      # drains pending dirt
    assert st.bank_refresher is None
    assert st.device_bank.published.n == len(st)
    u2, _ = st.search_batch(q, 5, impl="device")
    nu, _ = st.search_batch(q, 5, impl="numpy")
    for a, b in zip(u2, nu):
        assert set(a.tolist()) == set(b.tolist())


def test_epoch_slicing_keeps_posthandoff_writes_for_next_epoch():
    """A write landing between begin_epoch and flip is NOT half-included:
    it stays pending and lands wholly in the next epoch."""
    st = _store_with_rows()
    q = _embs(3, seed=7)
    ref = st.set_bank_refresh("async", thread=False)
    ref.refresh_once()
    epoch = None
    st.upgrade_batch([1], _embs(1, seed=14))
    epoch = ref.begin_epoch()
    assert epoch.rows.tolist() == [1]
    st.upgrade_batch([2], _embs(1, seed=15))         # after the handoff
    ref.apply(epoch)
    ref.flip(epoch)
    assert ref.lag()[0] == 1                         # row 2 still pending
    assert ref.refresh_once()                        # next epoch takes it
    assert ref.lag()[0] == 0


def test_apply_failure_requeues_dirty_rows():
    """An epoch that dies after consuming the dirty slice must put the rows
    back — they cannot silently vanish from every later refresh."""
    st = _store_with_rows()
    q = _embs(3, seed=8)
    ref = st.set_bank_refresh("async", thread=False)
    ref.refresh_once()
    st.upgrade_batch([3, 4], _embs(2, seed=16))
    real = st.device_bank.apply_rows
    calls = {"n": 0}

    def boom(*a, **kw):
        calls["n"] += 1
        raise RuntimeError("injected device failure")

    st.device_bank.apply_rows = boom
    with pytest.raises(RuntimeError):
        ref.refresh_once()
    st.device_bank.apply_rows = real
    assert calls["n"] == 1
    assert ref.lag()[0] == 2                          # rows requeued
    assert ref.refresh_once()
    u, _ = st.search_batch(q, 5, impl="device", freshness="stale")
    nu, _ = st.search_batch(q, 5, impl="numpy")
    for a, b in zip(u, nu):
        assert set(a.tolist()) == set(b.tolist())


def test_background_epoch_failure_surfaces_on_the_next_query():
    """A failed epoch on the refresh thread stops the thread and is raised
    by the next query, instead of the bank silently falling behind."""
    st = _store_with_rows()
    q = _embs(2, seed=3)
    st.search_batch(q, 5, impl="device")             # publish generation 1
    ref = st.set_bank_refresh("async", max_lag_rows=0)

    def boom(*a, **kw):
        raise RuntimeError("injected device failure")

    st.device_bank.apply_rows = boom
    st.upgrade_batch([3, 4], _embs(2, seed=16))
    st.kick_bank_refresh()
    ref._thread.join(timeout=10)
    assert not ref._thread.is_alive()
    assert isinstance(ref.failure, RuntimeError)
    assert ref.lag()[0] == 2                          # dirt was requeued
    with pytest.raises(RuntimeError, match="background bank refresh"):
        st.search_batch(q, 5, impl="device")
    del st.device_bank.apply_rows                     # device recovers
    st.set_bank_refresh("sync")                       # drain publishes it


def test_stale_snapshot_with_deleted_uid_does_not_crash_retrieval():
    """A lagging snapshot can surface a uid deleted since its generation;
    the retrieval pipeline must drop it before the live-embedding rounds
    instead of raising KeyError (regression: round 3's get_embeddings used
    to crash the whole query)."""
    from repro.core import retrieval as RT
    E = 32
    st = _store_with_rows(n=30, E=E)
    embs = _embs(30, E)
    st.set_bank_refresh("async", thread=False)
    target = embs[7]
    st.search_batch(target[None], 5, impl="device")  # publish generation 1
    st.delete_batch([7])                             # tail rows shift; uid 7 gone
    # raw stale search still names uid 7 (documented stale semantics)...
    u, _ = st.search_batch(target[None], 5, impl="device", freshness="stale")
    assert 7 in u.ravel().tolist()
    # ...but the pipeline filters it and completes
    res = RT.speculative_retrieve(st, [target], fine_query=target, k=5,
                                  refine_fn=None, impl="device",
                                  freshness="stale")
    assert 7 not in res.uids.tolist()
    assert 7 not in res.filtered_uids.tolist()
    # fresh-path delete of the LAST row marks nothing dirty (pending == 0)
    # yet must also not leak the dead uid through the policy path
    last_uid = int(st.uids()[-1])
    st.search_batch(target[None], 5, impl="device", freshness="fresh")
    st.delete_batch([last_uid])
    res = RT.speculative_retrieve(st, [target], fine_query=target, k=30,
                                  refine_fn=None, impl="device")
    assert last_uid not in res.filtered_uids.tolist()
    st.set_bank_refresh("sync")


def test_failed_growth_epoch_retries_cleanly():
    """A grow epoch that dies mid-scatter must not commit the new device
    capacity: the requeued retry has to grow again, not scatter past the
    old buffer's bounds (where .at[].set drops rows silently)."""
    E = 32
    st = EmbeddingStore(E, capacity=8)
    st.add_batch(np.arange(40), _embs(40, E), np.zeros(40), np.ones(40))
    q = _embs(2, E, seed=21)
    ref = st.set_bank_refresh("async", thread=False)
    st.search_batch(q, 5, impl="device")
    cap0 = st.device_bank.capacity
    # grow the host slab past device capacity, then fail the first epoch
    st.add_batch(np.arange(100, 200), _embs(100, E, seed=22), np.zeros(100),
                 np.ones(100))
    bank = st.device_bank
    real_scatter = bank._scatter_donated
    calls = {"n": 0}

    def boom(*a, **kw):
        calls["n"] += 1
        raise RuntimeError("injected failure mid-grow")

    bank._scatter_donated = boom
    with pytest.raises(RuntimeError):
        ref.refresh_once()
    bank._scatter_donated = real_scatter
    assert bank.capacity == cap0            # growth NOT committed
    assert ref.lag()[0] == 100              # rows requeued
    assert ref.refresh_once()               # retry grows again and succeeds
    assert bank.capacity > cap0
    u, _ = st.search_batch(q, 8, impl="device", freshness="stale")
    nu, _ = st.search_batch(q, 8, impl="numpy")
    for a, b in zip(u, nu):
        assert set(a.tolist()) == set(b.tolist())
    st.set_bank_refresh("sync")


def test_sync_query_during_scheduler_teardown_is_serialized():
    """set_bank_refresh('sync') drains while queries still route through
    the scheduler, and bank.sync + scheduler epochs share the bank's
    refresh lock — hammer the switch while a scanner runs to catch
    unserialized generation minting (the publish assert would fire)."""
    E = 32
    st = _store_with_rows(n=60, E=E)
    q = _embs(3, E, seed=23)
    st.search_batch(q, 5, impl="device")
    errors = []
    stop = threading.Event()

    def scanner():
        try:
            while not stop.is_set():
                st.search_batch(q, 5, impl="device")
        except Exception as e:  # pragma: no cover - the regression
            errors.append(e)

    t = threading.Thread(target=scanner)
    t.start()
    try:
        for i in range(12):
            st.set_bank_refresh("async", max_lag_rows=0)
            st.upgrade_batch([i % 60], _embs(1, E, seed=50 + i))
            st.set_bank_refresh("sync")
    finally:
        stop.set()
        t.join(timeout=30)
    assert not errors, errors
    u, _ = st.search_batch(q, 5, impl="device")
    nu, _ = st.search_batch(q, 5, impl="numpy")
    for a, b in zip(u, nu):
        assert set(a.tolist()) == set(b.tolist())


def test_staleness_accounting_exact():
    """Pending-row count and oldest-write timestamp must track DISTINCT
    dirty rows exactly: duplicate uids in one batch count once, and
    draining pending to zero (via delete) resets the age stamp so later
    writes don't inherit an ancient lag."""
    st = _store_with_rows(n=10)
    ref = st.set_bank_refresh("async", thread=False)
    ref.refresh_once()
    st.add_batch([7, 7], _embs(2, seed=30), [0, 0], [1, 1])  # same row twice
    assert ref.lag()[0] == 1
    st.upgrade_batch([7, 7], _embs(2, seed=31))              # still one row
    assert ref.lag()[0] == 1
    ref.refresh_once()
    # dirty a fresh row, then delete it while it's the tail: pending
    # returns to 0 and the age stamp must clear with it
    st.add_batch([99], _embs(1, seed=32), [0], [1])
    assert ref.lag()[0] == 1
    st.delete_batch([99])
    assert ref.lag() == (0, 0.0)
    assert st._bank_first_dirty_t is None
    time.sleep(0.02)
    st.upgrade_batch([3], _embs(1, seed=33))
    rows, ms = ref.lag()
    assert rows == 1 and ms < 15.0           # fresh stamp, not the old one
    st.set_bank_refresh("sync")


def test_delete_shrinks_published_n_and_tail_is_masked():
    st = _store_with_rows(n=20)
    q = _embs(3, seed=4)
    st.set_bank_refresh("async", thread=False)
    st.search_batch(q, 5, impl="device")
    st.delete_batch([0, 19, 7])
    u, _ = st.search_batch(q, 25, impl="device", freshness="fresh")
    assert st.device_bank.published.n == 17
    assert u.shape == (3, 17)
    assert not {0, 19, 7} & set(u.ravel().tolist())


# ---------------------------------------------------------------------------
# real-thread smoke: the background scheduler under a mixed workload
# ---------------------------------------------------------------------------


def test_threaded_refresher_mixed_workload_converges():
    """Non-deterministic by nature (the enumerated harness carries the
    strong guarantees); this asserts liveness + internal consistency with a
    REAL background thread: scans always see a whole published generation,
    and after quiesce the bank equals the host exactly."""
    E = 32
    st = _store_with_rows(n=80, E=E)
    q = _embs(4, E, seed=3)
    ref = st.set_bank_refresh("async", max_lag_rows=64)
    st.search_batch(q, 5, impl="device")
    rng = np.random.default_rng(0)
    stop = threading.Event()
    errors = []

    def writer():
        try:
            i = 0
            while not stop.is_set():
                kind = i % 3
                if kind == 0:
                    st.add_batch([2000 + i], _embs(1, E, seed=100 + i),
                                 [0], [1])
                elif kind == 1:
                    st.upgrade_batch([int(rng.integers(0, 80))],
                                     _embs(1, E, seed=200 + i))
                else:
                    uid = 2000 + i - 2
                    if st.has_cached(uid) or True:
                        try:
                            st.delete_batch([uid])
                        except KeyError:
                            pass
                i += 1
                time.sleep(0.001)
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    t = threading.Thread(target=writer)
    t.start()
    try:
        for _ in range(60):
            u, s = st.search_batch(q, 5, impl="device")
            # internal consistency of one generation: k results per query,
            # descending scores, uids drawn from that snapshot
            assert u.shape == (4, 5)
            assert (np.diff(s, axis=1) <= 1e-6).all()
    finally:
        stop.set()
        t.join(timeout=30)
    assert not errors, errors
    # quiesce: drain and compare against the sync path exactly
    st.set_bank_refresh("sync")
    u, _ = st.search_batch(q, 5, impl="device")
    nu, _ = st.search_batch(q, 5, impl="numpy")
    for a, b in zip(u, nu):
        assert set(a.tolist()) == set(b.tolist())
    assert ref.n_epochs > 0
