"""Device-resident embedding bank: the searchable copy of the store's int4
slab, kept on-accelerator and refreshed *incrementally*.

Why it exists
-------------
After PR 1 the serving hot path still re-uploaded the whole fp32 dense slab
to the device on every ``search_batch`` call (``jnp.asarray(slab)``) and kept
that fp32 copy — 8x the int4 footprint — purely to feed the scan. On
accelerators the dominant query cost is that H2D transfer. ``DeviceBank``
makes the *quantized* slab itself the searchable index:

  * ``_packed`` (cap, E//2) int8 + ``_scales`` (cap, 1) fp32 live on device
    (row-sharded across ``devices`` when more than one is given),
  * queries run the fused dequant-and-scan ``retrieval_topk_int4`` — rows
    dequantize block-wise in VMEM/cache right before scoring, so the fp32
    bank never materializes anywhere,
  * refresh scatters ONLY rows dirtied since the last sync
    (``jax.Array.at[rows].set`` — the host payload is just the dirty rows;
    the scatter publishes a fresh device buffer copy-on-write so in-flight
    scans keep their snapshot), and grows by slab-doubling *on device* in
    lockstep with the host slab (a device-to-device copy, no re-upload).

Refresh protocol & consistency
------------------------------
``DeviceBank`` is not thread-safe on its own; refreshes are serialized by
the caller (``EmbeddingStore`` under its mutation lock in sync mode, or a
single ``RefreshScheduler`` epoch at a time in async mode — see
``repro.core.bank_refresh``):

  1. The store keeps a per-bank dirty bitmap (``_bank_dirty``) set by
     ``add_batch`` / ``upgrade_batch`` / ``delete_batch`` alongside the
     dense-cache dirty bits.
  2. A refresh is split into two phases so it can run double-buffered:
     ``apply_rows`` builds the *shadow* snapshot (device-side capacity
     doubling if the host slab grew, then a scatter of the dirty rows'
     packed nibbles + scales — async ``device_put`` of just those rows)
     WITHOUT touching the published state, and ``publish`` flips the
     published pointer to it in one atomic attribute write. ``sync`` is
     the fused convenience (apply + publish) used by the in-lock path.
  3. The scan runs with no lock at all: ``search`` reads one
     ``BankSnapshot`` (packed, scales, n, uids, generation) atomically,
     and the arrays inside are immutable — a concurrent flip can only
     install the *next* snapshot, so an in-flight query sees a
     stale-but-matched generation, never torn rows or mismatched halves.

Hence the guarantee: after a flip, device bank row i equals the host slab
row i bit-exactly for every i < n at that epoch's begin point, and every
query sees exactly the state of ONE published generation.

Double buffering & donation: the scatter into the shadow never mutates the
published buffers (publishing is copy-on-write), so scans overlap refreshes
freely. When the refresh grew capacity, the intermediate grown buffers are
private to the refresher and the follow-up scatter donates them
(``_scatter_donated``) instead of allocating a third copy.

Transfer accounting: ``h2d_bytes`` / ``h2d_rows`` count the actual
host-to-device payload (scattered rows + scales + indices). Steady-state
queries transfer nothing — ``benchmarks/store_scale.py`` asserts the
delta is exactly zero after warm-up.

Sharded search (``len(devices) > 1``): rows are partitioned contiguously
across a 1-D ``bank`` mesh; each shard runs the fused scan over its slice
and the per-shard (Q, k) winners are merged with one small all-gather
(``distributed.collectives.topk_allgather_merge``) — wire cost independent
of bank size. The IVF pruned entries (``search_rows``/``search_gathered``)
shard-route the same way: the candidate set is partitioned by row
ownership (``repro.index.pruned_scan.partition_rows_by_shard``) or masked
per shard, each shard scans only its local candidates with per-shard
``n_valid`` masking, and the partials merge through the same collective.
"""
from __future__ import annotations

import threading
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import spans
from repro.distributed.collectives import topk_allgather_merge
from repro.kernels.retrieval_topk.kernel import (
    retrieval_topk_int4_gathered_pallas, retrieval_topk_int4_pallas)
from repro.kernels.retrieval_topk.ops import (resolve_impl, retrieval_topk,
                                              retrieval_topk_int4,
                                              retrieval_topk_int4_gathered,
                                              retrieval_topk_int4_rows)
from repro.kernels.retrieval_topk.ref import (
    retrieval_topk_int4_blocked, retrieval_topk_int4_gathered_blocked,
    retrieval_topk_reference)


class BankSnapshot(NamedTuple):
    """One published generation of the device bank. The arrays are immutable
    jax buffers and ``uids`` is a private host copy, so holding a snapshot
    pins a complete, internally consistent view of the bank at one refresh
    point — later flips never retarget it."""
    packed: jax.Array    # (cap', E//2) int8 (or (cap', E) fp32 in debug mode)
    scales: jax.Array    # (cap', 1) fp32
    n: int               # valid rows; rows >= n are masked at query time
    uids: np.ndarray     # (n,) int64, row i -> uid, aligned with this epoch
    generation: int      # monotonically increasing flip counter


# scatter jits shared across DeviceBank instances (single-device layout —
# the sharded path pins out_shardings per mesh and stays per-instance).
# Copy-on-write: the published input buffer survives for in-flight scans.
_scatter_cow = jax.jit(lambda a, r, v: a.at[r].set(v))
# donating variant, safe ONLY when the input buffer is private to the
# refresher (e.g. the freshly grown shadow) — never for a published buffer
_scatter_donated = jax.jit(lambda a, r, v: a.at[r].set(v),
                           donate_argnums=(0,))


class DeviceBank:
    """Device-resident (optionally sharded) searchable slab mirror.

    ``store_int4=True`` mirrors the packed int4 + scales layout of
    ``EmbeddingStore``; ``store_int4=False`` mirrors fp32 rows (debug mode)
    and searches them with the dense kernel instead of the fused dequant
    scan. See module docstring for the refresh protocol.

    ``impl``/``interpret`` are resolved once, here, from the platform of
    ``devices`` (``ops.resolve_impl``): on a TPU every scan of this bank
    runs the compiled Pallas kernel, and ``(bank.impl, bank.interpret)``
    names what served it.
    """

    def __init__(self, embed_dim: int, *, store_int4: bool = True,
                 devices: Optional[Sequence[jax.Device]] = None,
                 impl: str = "auto", interpret: Optional[bool] = None,
                 block_n: int = 4096):
        self.embed_dim = embed_dim
        self.store_int4 = store_int4
        devs = list(devices) if devices is not None else list(jax.devices())
        self.devices = devs
        self.n_shards = len(devs)
        self.mesh = Mesh(np.array(devs), ("bank",))
        self._sh_rows = NamedSharding(self.mesh, P("bank"))
        self._row_width = embed_dim // 2 if store_int4 else embed_dim
        self._row_dtype = jnp.int8 if store_int4 else jnp.float32
        self.impl, self.interpret = resolve_impl(
            impl, interpret, int4=store_int4, platform=devs[0].platform)
        self.block_n = block_n
        self._cap = 0
        # the published BankSnapshot, swapped as ONE object: a reader
        # (search) grabs it in a single atomic attribute read, so a flip
        # racing a scan can only hand it a stale-but-matched generation,
        # never a torn packed/scales/uids combination
        self._published: Optional[BankSnapshot] = None
        self._gen = 0
        # serializes whole refreshes (apply + publish) across DRIVERS: the
        # in-lock sync path and an async scheduler epoch must never mint
        # generations concurrently (each bases its shadow on what it thinks
        # is the latest published state — unserialized, one would drop the
        # other's rows). Scans never take it.
        self.refresh_lock = threading.RLock()
        # copy-on-write scatter: the update lands in a fresh device buffer
        # (device-to-device; the host payload is still only the dirty rows).
        # NOT donated — an in-flight search may still hold the old snapshot,
        # and donation would invalidate it under its feet. Single-device
        # banks share the module-level jits; the sharded layout pins
        # out_shardings per mesh.
        if self.n_shards == 1:
            self._scatter = _scatter_cow
            self._scatter_donated = _scatter_donated
        else:
            self._scatter = jax.jit(lambda a, r, v: a.at[r].set(v),
                                    out_shardings=self._sh_rows)
            self._scatter_donated = jax.jit(
                lambda a, r, v: a.at[r].set(v),
                out_shardings=self._sh_rows, donate_argnums=(0,))
        self._search_fns: Dict = {}
        # host->device transfer accounting (see module docstring)
        self.h2d_bytes = 0
        self.h2d_rows = 0
        self.n_syncs = 0
        self.n_grows = 0
        self.n_warms = 0
        # (nq, k, kw) of the most recent search: the async refresher replays
        # this shape against a grown shadow snapshot to pre-compile the
        # search executable off the query path (see ``warm``)
        self._warm_hint: Optional[Tuple[int, int, tuple]] = None

    # -- state ---------------------------------------------------------------

    def __len__(self) -> int:
        st = self._published
        return 0 if st is None else st.n

    @property
    def capacity(self) -> int:
        return self._cap

    @property
    def published(self) -> Optional[BankSnapshot]:
        """The live snapshot (atomic read; may lag the host in async mode)."""
        return self._published

    @property
    def generation(self) -> int:
        st = self._published
        return 0 if st is None else st.generation

    def stats(self) -> Dict[str, int]:
        st = self._published
        return {"h2d_bytes": self.h2d_bytes, "h2d_rows": self.h2d_rows,
                "n_syncs": self.n_syncs, "n_grows": self.n_grows,
                "capacity": self._cap, "n": len(self),
                "n_shards": self.n_shards, "generation": self.generation,
                "device_bytes": 0 if st is None else
                int(st.packed.nbytes + st.scales.nbytes)}

    def device_bytes(self) -> int:
        return self.stats()["device_bytes"]

    # -- refresh -------------------------------------------------------------

    def _device_zeros(self, shape, dtype) -> jax.Array:
        return jax.device_put(jnp.zeros(shape, dtype), self._sh_rows)

    def _grow_to(self, packed, scales, cap: int):
        """Slab-doubling on device, in lockstep with the host slab: allocate
        the doubled buffers and copy the old content device-to-device —
        never a host re-upload. Returns the grown (packed, scales). Pure
        w.r.t. bank state: ``self._cap`` is committed by the caller only
        after the whole epoch's device work succeeded, so a failed grow
        epoch retries from scratch instead of scattering past the old
        buffer's bounds."""
        old_cap = self._cap
        new_p = self._device_zeros((cap, self._row_width), self._row_dtype)
        new_s = self._device_zeros((cap, 1), jnp.float32)
        if packed is not None and old_cap:
            new_p = jax.device_put(new_p.at[:old_cap].set(packed),
                                   self._sh_rows)
            new_s = jax.device_put(new_s.at[:old_cap].set(scales),
                                   self._sh_rows)
        return new_p, new_s

    def apply_rows(self, host_cap: int, dirty_rows: np.ndarray,
                   vals: np.ndarray, scs: np.ndarray, n: int,
                   uids: np.ndarray) -> BankSnapshot:
        """Build the SHADOW snapshot: grow device capacity to match
        ``host_cap`` if the host slab doubled, then scatter the dirty rows'
        payload (``vals``/``scs`` are host copies of those rows, taken at
        epoch begin so a concurrent writer can't change them under the
        dispatch). The published state is untouched — callers flip it with
        ``publish``. Refreshes must be serialized by the caller (the store
        lock in sync mode, the scheduler's epoch lock in async mode); scans
        need no serialization at all."""
        base = self._published
        packed, scales = ((None, None) if base is None
                          else (base.packed, base.scales))
        # device capacity = host capacity rounded up to a multiple of the
        # shard count (padded rows are masked by n_valid at query time)
        cap = int(host_cap)
        cap += (-cap) % self.n_shards
        old_cap = self._cap
        private = cap > old_cap  # grown buffers have no readers -> donatable
        if private:
            packed, scales = self._grow_to(packed, scales, cap)
        dirty_rows = np.asarray(dirty_rows, np.int64).ravel()
        if dirty_rows.size:
            # pad the scatter to a pow2 bucket (duplicate last row:
            # scattering the same value twice is idempotent) so jit retraces
            # O(log N) distinct shapes instead of one per dirty count
            m = int(dirty_rows.size)
            bucket = 1 << (m - 1).bit_length()
            pad = bucket - m
            rows = np.concatenate([dirty_rows, np.full(pad, dirty_rows[-1])])
            rows32 = rows.astype(np.int32)
            pad_sel = np.concatenate([np.arange(m), np.full(pad, m - 1)])
            vals = np.ascontiguousarray(vals[pad_sel])
            scs = np.ascontiguousarray(scs[pad_sel])
            scatter = self._scatter_donated if private else self._scatter
            packed = scatter(packed, rows32, vals)
            scales = self._scatter_donated(scales, rows32, scs) if private \
                else self._scatter(scales, rows32, scs)
            nbytes = int(vals.nbytes + scs.nbytes + 2 * rows32.nbytes)
            self.h2d_bytes += nbytes
            spans.count("h2d_bytes", nbytes)
            self.h2d_rows += m
        if private:
            # commit the growth only now that every dispatch above was
            # accepted: an exception mid-epoch leaves _cap at the published
            # buffers' size, so the requeued retry grows again instead of
            # scattering out-of-bounds (silently dropped by .at[].set)
            self._cap = cap
            if base is not None and old_cap:
                self.n_grows += 1
            self._search_fns.clear()  # traced shapes changed (O(log N)x)
        self._gen += 1
        return BankSnapshot(packed, scales, int(n),
                            np.asarray(uids, np.int64), self._gen)

    def publish(self, snap: BankSnapshot) -> BankSnapshot:
        """Atomically flip the published pointer to ``snap`` (all-or-nothing:
        one attribute write installs packed+scales+n+uids+generation
        together). In-flight scans keep whatever snapshot they already
        read. Generations must advance: an out-of-order flip means two
        refreshes ran concurrently (each based on what it *thought* was the
        latest state) and one of them dropped rows — refresh drivers
        serialize whole epochs precisely to make this unreachable, so fail
        loudly rather than serve a bank missing updates."""
        cur = self._published
        assert cur is None or snap.generation > cur.generation, (
            f"out-of-order flip: generation {snap.generation} after "
            f"{cur.generation} — refresh epochs must be serialized")
        self._published = snap
        self.n_syncs += 1
        return snap

    def warm(self, state: BankSnapshot) -> bool:
        """Pre-compile the search path for ``state``'s array shapes,
        replaying the last-seen query shape. A capacity change invalidates
        the traced search executable, and the retrace + compile costs
        10-20x a steady scan — the sync path pays that inline on the first
        post-growth query (it grows under the store lock on the query
        path, so it structurally cannot hide it); the async refresher
        calls this on the SHADOW snapshot before the flip, so queries
        never see the spike. The single-device int4 path compiles
        ahead-of-time without executing (``warm_retrieval_topk_int4``);
        the sharded/fp32 paths warm by running one dummy scan. Returns
        False when no query shape has been observed yet."""
        hint = self._warm_hint
        if hint is None or state.n == 0:
            return False
        nq, k, kw = hint
        k = min(k, state.n)
        if self.store_int4 and self.n_shards == 1:
            from repro.kernels.retrieval_topk.ops import (
                warm_retrieval_topk_int4)
            warm_retrieval_topk_int4(
                (nq, self.embed_dim), tuple(state.packed.shape), k,
                normalize=False, impl=self.impl, interpret=self.interpret,
                **dict({"block_n": self.block_n}, **dict(kw)))
        else:
            dummy = np.zeros((nq, self.embed_dim), np.float32)
            self.search(dummy, k, state=state, **dict(kw))
        self.n_warms += 1
        return True

    def sync(self, host_packed: np.ndarray, host_scales: np.ndarray,
             n: int, dirty_rows: np.ndarray,
             uids: Optional[np.ndarray] = None) -> BankSnapshot:
        """Fused apply + flip (the in-lock sync path): bring the device slab
        up to date with the host slab and publish. Caller must hold the
        store's mutation lock; ``dirty_rows`` are the row indices written
        since the last refresh — only those rows travel. Returns the new
        snapshot; pass it to ``search(state=...)`` to pin a scan to this
        sync point."""
        dirty_rows = np.asarray(dirty_rows, np.int64).ravel()
        if uids is None:
            uids = np.zeros((int(n),), np.int64)
        with self.refresh_lock:
            snap = self.apply_rows(host_packed.shape[0], dirty_rows,
                                   host_packed[dirty_rows],
                                   host_scales[dirty_rows], n, uids)
            return self.publish(snap)

    # -- search --------------------------------------------------------------

    def _sharded_search_fn(self, k: int, impl: str, cap: int):
        """Jitted shard_map search for a snapshot's capacity: per-shard
        fused top-k over the local rows, one small all-gather merge."""
        key = (k, cap, impl)
        fn = self._search_fns.get(key)
        if fn is not None:
            return fn
        rps = cap // self.n_shards
        k_loc = min(k, rps)
        int4 = self.store_int4
        block_n = self.block_n
        interpret = self.interpret

        def local(q, p, sc, n):
            sid = jax.lax.axis_index("bank")
            n_loc = jnp.clip(n - sid * rps, 0, rps).astype(jnp.int32)
            if int4:
                if impl == "pallas":
                    s, i = retrieval_topk_int4_pallas(
                        q, p, sc, k_loc, normalize=False, n_valid=n_loc,
                        interpret=interpret)
                else:
                    s, i = retrieval_topk_int4_blocked(
                        q, p, sc, k_loc, normalize=False, block_n=block_n,
                        n_valid=n_loc)
            else:
                s, i = retrieval_topk_reference(q, p, k_loc, normalize=False,
                                                n_valid=n_loc)
            gids = i + (sid * rps).astype(jnp.int32)
            return topk_allgather_merge(s, gids, k, "bank")

        mesh = self.mesh

        def search(q, p, sc, n):
            return jax.shard_map(local, mesh=mesh,
                                 in_specs=(P(), P("bank"), P("bank"), P()),
                                 out_specs=(P(), P()), check_vma=False)(
                                     q, p, sc, n)

        fn = jax.jit(search)
        self._search_fns[key] = fn
        return fn

    def search(self, queries: np.ndarray, k: int,
               state: Optional[BankSnapshot] = None, **kw
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Fused top-k over the device-resident bank: (Q, E) queries ->
        (row indices (Q, k) int64, scores (Q, k) fp32), descending score.
        Zero host->device slab traffic — only the query batch travels.
        Scans ONE published ``BankSnapshot`` — pass the snapshot a refresh
        returned to pin the scan to that generation (the store does,
        keeping row indices aligned with the snapshot's uids); defaults to
        the latest. Extra ``kw`` are kernel tuning knobs (block_q, ...)
        forwarded to the single-device scan; the sharded path configures its
        kernel at bank construction (``block_n``) and rejects them."""
        if state is None:
            state = self._published
        assert state is not None, "sync() before search()"
        self._warm_hint = (int(np.asarray(queries).shape[0]), int(k),
                           tuple(sorted(kw.items())))
        packed, scales, n = state.packed, state.scales, state.n
        k = min(k, n)
        q = spans.to_device(np.asarray(queries, np.float32))
        impl = self.impl
        if self.n_shards == 1:
            if self.store_int4:
                s, i = retrieval_topk_int4(q, packed, scales, k,
                                           normalize=False, impl=impl,
                                           interpret=self.interpret,
                                           n_valid=n,
                                           **dict({"block_n": self.block_n},
                                                  **kw))
            else:
                s, i = retrieval_topk(q, packed, k, normalize=False,
                                      impl=impl, interpret=self.interpret,
                                      n_valid=n, **kw)
        else:
            if kw:
                raise ValueError("sharded DeviceBank.search takes no kernel "
                                 f"kwargs (got {sorted(kw)}); set block_n "
                                 "at attach_device_bank time")
            s, i = self._sharded_search_fn(k, impl, packed.shape[0])(
                q, packed, scales, jnp.asarray(n, jnp.int32))
        return (spans.to_host(i).astype(np.int64, copy=False),
                spans.to_host(s).astype(np.float32, copy=False))

    def _sharded_rows_fn(self, k: int, k_loc: int, impl: str, cap: int,
                         m_width: int):
        """Jitted shard_map pruned scan (batch-union strategy) for one
        (k, candidate-width, capacity): each shard gathers ITS slice of the
        routed candidate set (``m_width`` shard-local rows, live entries
        first), runs the same fused int4 dequant-and-scan as the exhaustive
        path with per-shard ``n_valid`` = its live candidate count, and the
        per-shard (Q, k_loc) winners merge through one small all-gather.
        Per-shard work scales with its candidate share, not the bank size —
        the same >= 3x pruning shape the single-shard path asserts."""
        key = ("rows", k, k_loc, cap, m_width, impl)
        fn = self._search_fns.get(key)
        if fn is not None:
            return fn
        rps = cap // self.n_shards
        block_n = self.block_n
        interpret = self.interpret

        def local(q, p, sc, rows, m):
            sid = jax.lax.axis_index("bank")
            rloc = rows[0]                 # (M,) shard-local candidate rows
            mloc = m[0]                    # () live candidates this shard
            gp = jnp.take(p, rloc, axis=0)        # (M, E//2) int4 bytes
            gs = jnp.take(sc, rloc, axis=0)       # (M, 1)
            if impl == "pallas":
                s, i = retrieval_topk_int4_pallas(
                    q, gp, gs, k_loc, normalize=False, n_valid=mloc,
                    interpret=interpret)
            else:
                s, i = retrieval_topk_int4_blocked(
                    q, gp, gs, k_loc, normalize=False, block_n=block_n,
                    n_valid=mloc)
            gids = jnp.take(rloc, i) + (sid * rps).astype(jnp.int32)
            # a shard short of k_loc live candidates pads with sentinel
            # scores; those slots must not surface a real row id
            gids = jnp.where(s > -5e29, gids, -1)
            return topk_allgather_merge(s, gids, k, "bank")

        mesh = self.mesh

        def search(q, p, sc, rows, m):
            return jax.shard_map(local, mesh=mesh,
                                 in_specs=(P(), P("bank"), P("bank"),
                                           P("bank"), P("bank")),
                                 out_specs=(P(), P()), check_vma=False)(
                                     q, p, sc, rows, m)

        fn = jax.jit(search)
        self._search_fns[key] = fn
        return fn

    def _sharded_gathered_fn(self, k: int, impl: str, cap: int, width: int):
        """Jitted shard_map pruned scan (per-query strategy): the (Q, L)
        global candidate matrix is replicated; each shard translates it to
        shard-local row ids, masks candidates it does not own (or past its
        local fill) to -1, scans its gathered blocks with the per-query
        fused kernel, and the per-shard winners merge via all-gather. Every
        shard walks the full (Q, L) id matrix but gathers/dequantizes only
        its own rows' payload."""
        key = ("gathered", k, cap, width, impl)
        fn = self._search_fns.get(key)
        if fn is not None:
            return fn
        rps = cap // self.n_shards
        interpret = self.interpret

        def local(q, p, sc, ids, n):
            sid = jax.lax.axis_index("bank")
            base = (sid * rps).astype(jnp.int32)
            n_loc = jnp.clip(n - base, 0, rps).astype(jnp.int32)
            lid = ids - base
            lid = jnp.where((ids >= 0) & (lid >= 0) & (lid < rps), lid, -1)
            if impl == "pallas":
                safe = jnp.clip(lid, 0, rps - 1)
                gp = jnp.take(p, safe, axis=0)    # (Q, L, E//2) int4 bytes
                gs = jnp.take(sc, safe, axis=0)   # (Q, L, 1)
                s, i = retrieval_topk_int4_gathered_pallas(
                    q, gp, gs, lid, k, n_valid=n_loc, interpret=interpret)
            else:
                s, i = retrieval_topk_int4_gathered_blocked(
                    q, p, sc, lid, k, normalize=False, n_valid=n_loc)
            gids = jnp.where(s > -5e29, i + base, -1)
            return topk_allgather_merge(s, gids, k, "bank")

        mesh = self.mesh

        def search(q, p, sc, ids, n):
            return jax.shard_map(local, mesh=mesh,
                                 in_specs=(P(), P("bank"), P("bank"), P(),
                                           P()),
                                 out_specs=(P(), P()), check_vma=False)(
                                     q, p, sc, ids, n)

        fn = jax.jit(search)
        self._search_fns[key] = fn
        return fn

    def search_gathered(self, queries: np.ndarray, row_ids: np.ndarray,
                        k: int, state: Optional[BankSnapshot] = None, **kw
                        ) -> Tuple[np.ndarray, np.ndarray]:
        """IVF pruned scan: fused top-k over per-query CANDIDATE rows of
        one published snapshot (``row_ids`` (Q, L) int32, -1 padded — the
        store builds it from the index's posting lists). Device work and
        HBM traffic scale with L, not the bank size; the gather itself is
        int4-sized and runs inside the same jit as the scan, so the fp32
        bank still never materializes. Ids past the snapshot's fill level
        are masked (posting lists may run ahead of a stale generation).
        Returns ((Q, k) GLOBAL row ids, (Q, k) scores); slots with no live
        candidate hold id -1 / score -1e30. On a row-sharded bank each
        shard masks the candidates it does not own, scans its local
        gathered blocks, and the per-shard winners merge via
        ``topk_allgather_merge`` (kernel kwargs are rejected there, like
        ``search``). Requires an int4 bank."""
        if state is None:
            state = self._published
        assert state is not None, "sync() before search_gathered()"
        if not self.store_int4:
            raise NotImplementedError("pruned search needs an int4 bank")
        k = min(k, state.n)
        q = spans.to_device(np.asarray(queries, np.float32))
        if self.n_shards == 1:
            s, i = retrieval_topk_int4_gathered(
                q, state.packed, state.scales, row_ids, k, normalize=False,
                impl=self.impl, interpret=self.interpret, n_valid=state.n,
                **kw)
            return (spans.to_host(i).astype(np.int64, copy=False),
                    spans.to_host(s).astype(np.float32, copy=False))
        if kw:
            raise ValueError("sharded DeviceBank.search_gathered takes no "
                             f"kernel kwargs (got {sorted(kw)})")
        row_ids = np.asarray(row_ids, np.int32)
        if row_ids.shape[1] < k:  # top-k needs >= k columns (-1 = masked)
            row_ids = np.pad(row_ids, ((0, 0), (0, k - row_ids.shape[1])),
                             constant_values=-1)
        fn = self._sharded_gathered_fn(k, self.impl,
                                       state.packed.shape[0],
                                       row_ids.shape[1])
        s, i = fn(q, state.packed, state.scales, jnp.asarray(row_ids),
                  jnp.asarray(state.n, jnp.int32))
        return (spans.to_host(i).astype(np.int64, copy=False),
                spans.to_host(s).astype(np.float32, copy=False))

    def search_rows(self, queries: np.ndarray, rows: np.ndarray, k: int,
                    state: Optional[BankSnapshot] = None, **kw
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """IVF pruned scan, batch-union strategy: one shared candidate-row
        set for the whole batch — a single int4-sized gather feeds the
        SAME fused dequant-and-scan the exhaustive path runs, over
        ``len(rows)`` instead of ``n`` rows. The caller pre-filters
        ``rows`` to ``< state.n`` (the union comes from current posting
        lists, the scan from one published snapshot). On a row-sharded
        bank the union is routed by shard ownership
        (``pruned_scan.partition_rows_by_shard``): each shard scans only
        its shard-local candidate slice and the partial top-k merge via
        ``topk_allgather_merge`` (kernel kwargs are rejected there, like
        ``search``). Returns ((Q, k) GLOBAL row ids, (Q, k) scores); a
        slot with no live candidate (only reachable when the total live
        candidate count < k) holds id -1 / score -1e30. Requires
        k <= len(rows) and an int4 bank."""
        if state is None:
            state = self._published
        assert state is not None, "sync() before search_rows()"
        if not self.store_int4:
            raise NotImplementedError("pruned search needs an int4 bank")
        q = spans.to_device(np.asarray(queries, np.float32))
        if self.n_shards == 1:
            s, i = retrieval_topk_int4_rows(
                q, state.packed, state.scales, rows, k, normalize=False,
                impl=self.impl, interpret=self.interpret, **kw)
            rows = np.asarray(rows, np.int64)
            return (rows[spans.to_host(i).astype(np.int64, copy=False)],
                    spans.to_host(s).astype(np.float32, copy=False))
        if kw:
            raise ValueError("sharded DeviceBank.search_rows takes no "
                             f"kernel kwargs (got {sorted(kw)}); set "
                             "block_n at attach_device_bank time")
        from repro.index.pruned_scan import partition_rows_by_shard
        cap = state.packed.shape[0]
        local, counts = partition_rows_by_shard(rows, cap // self.n_shards,
                                                self.n_shards)
        k_loc = min(k, local.shape[1])
        fn = self._sharded_rows_fn(k, k_loc, self.impl, cap,
                                   local.shape[1])
        s, gids = fn(q, state.packed, state.scales, jnp.asarray(local),
                     jnp.asarray(counts))
        return np.asarray(gids, np.int64), np.asarray(s, np.float32)
