"""Query runtime (paper §2.2 "online recall", §3.4 speculative retrieval).

Embeds the query at several granularities (exit depths of the *query*
tower), speculatively filters the store per granularity, verifies globally,
then refines surviving coarse candidates with the live encoder under an
optional latency budget. Repeated queries hit permanently-upgraded
embeddings (§5.3) and skip refinement entirely.

Two entry points:
  * ``query``       — one query, full seed-compatible semantics (refinement
    budget counts *successes*, retrying past failed candidates).
  * ``query_batch`` — many users per drain: ONE ``mem_embed_all_exits`` tower
    pass for the whole batch, one fused ``store.search_batch`` call over all
    B×G (query, granularity) pairs, a single deduplicated refinement batch
    shared across queries, and one store ``upgrade_batch``. A candidate
    pending for several queries is refined once and counted for each; the
    per-query budget caps *attempted* candidates (rank order), a slight
    simplification of the sequential retry semantics.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MEMConfig, RecallConfig
from repro.core import spans
from repro.core.retrieval import (RetrievalResult, global_verify,
                                  refine_round, single_granularity_retrieve,
                                  speculative_retrieve)
from repro.core.store import EmbeddingStore


class QueryEngine:
    def __init__(self, params, cfg: MEMConfig, recall: RecallConfig, *,
                 store: EmbeddingStore,
                 refine_fn: Optional[Callable] = None,
                 query_modality: str = "text", lora=None,
                 fw_kw: Optional[dict] = None, search_impl: str = "auto",
                 search_devices=None, bank_refresh: str = "sync",
                 bank_max_lag_rows: Optional[int] = None,
                 bank_max_lag_ms: Optional[float] = None,
                 freshness: Optional[str] = None, index: str = "none",
                 index_clusters: int = 64,
                 index_min_rows: Optional[int] = None,
                 nprobe: Optional[int] = None,
                 index_auto_grow: bool = False):
        from repro.models import imagebind as IB
        self.params, self.cfg, self.recall = params, cfg, recall
        self.store = store
        self.refine_fn = refine_fn
        self.modality = query_modality
        self.lora = lora
        self.fw_kw = fw_kw or {}
        self.search_impl = search_impl
        # per-query default for the async staleness policy (None = obey the
        # configured bound; "fresh"/"stale" force a side)
        self.freshness = freshness
        # IVF probe fan-out forwarded to every store scan (None = the
        # index's configured default; ignored on non-IVF paths)
        self.nprobe = nprobe
        # coarse-filter index: "ivf" attaches the online IVF quantizer so
        # search_batch(impl='auto') cuts over to the pruned path at
        # index_min_rows; an index someone already attached is reused
        # (attach kwargs win only when we create it here)
        if index == "ivf":
            if store.ivf_index is None:
                # auto_grow keeps C tracking ~sqrt(n) across re-cluster
                # epochs instead of pinning the attach-time choice
                ivf_kw = {"n_clusters": index_clusters,
                          "auto_grow": index_auto_grow}
                if index_min_rows is not None:
                    ivf_kw["min_rows"] = index_min_rows
                if nprobe is not None:
                    ivf_kw["nprobe"] = nprobe
                store.attach_ivf(**ivf_kw)
        elif index != "none":
            raise ValueError(f"index={index!r}")
        if search_impl == "ivf" and store.ivf_index is None:
            raise ValueError("search_impl='ivf' needs an attached IVF "
                             "index (pass index='ivf' or attach_ivf "
                             "beforehand)")
        # device-resident bank: attach eagerly so the warm-up upload happens
        # at engine construction, not on the first query. An explicit device
        # list always (re)attaches — a bank auto-attached earlier over
        # different devices must not silently win over the caller's request.
        if search_devices is not None:
            store.attach_device_bank(search_devices)
            self.search_impl = "device"
        elif search_impl == "device" and store.device_bank is None:
            store.attach_device_bank()
        # bank refresh policy: "async" moves the dirty-row scatter off the
        # query path onto a background scheduler (bounded staleness);
        # "sync" keeps the exact in-lock refresh and leaves an existing
        # scheduler alone only if one was never configured here
        if bank_refresh == "async":
            store.set_bank_refresh("async", max_lag_rows=bank_max_lag_rows,
                                   max_lag_ms=bank_max_lag_ms)
        elif bank_refresh != "sync":
            raise ValueError(f"bank_refresh={bank_refresh!r}")
        t = cfg.tower(query_modality)
        exits = recall.exit_layers(t.n_layers)
        k = recall.query_granularities
        # spread query granularities across the exit range (incl. full depth)
        idx = np.unique(np.linspace(0, len(exits) - 1, k).round().astype(int))
        self.granularities = [exits[i] for i in idx]
        # weights as arguments, not closed over: a closure would bake them
        # into the executable as constants
        self._jit_all_exits = jax.jit(lambda p, lo, x: IB.mem_embed_all_exits(
            p, self.cfg, self.recall, self.modality, x, lora=lo,
            **self.fw_kw)["exit_embs"])
        self._exits = exits
        self._g_rows = [exits.index(g) for g in self.granularities]

    # -- embedding -----------------------------------------------------------

    def embed_query(self, query: np.ndarray) -> Dict[int, np.ndarray]:
        """One tower pass gives every granularity (exit taps are free)."""
        embs = np.asarray(self._jit_all_exits(
            self.params, self.lora, jnp.asarray(query[None])))[:, 0]
        return {e: embs[self._exits.index(e)] for e in self.granularities}

    def embed_query_batch(self, queries: np.ndarray) -> np.ndarray:
        """(B, ...) query batch -> (B, G, E) granularity embeddings from ONE
        tower pass (row -1 is the fine/full-depth embedding)."""
        with spans.span("query.embed"):
            embs = spans.to_host(self._jit_all_exits(
                self.params, self.lora, spans.to_device(queries)))
        return embs[self._g_rows].transpose(1, 0, 2)  # (B, G, E)

    # -- single query --------------------------------------------------------

    def query(self, query: np.ndarray, *, k: int = 10, final_k: int = 10,
              refine_budget: Optional[int] = None,
              speculative: bool = True) -> RetrievalResult:
        by_g = self.embed_query(query)
        fine = by_g[self.granularities[-1]]
        if not speculative:
            with spans.span("query.filter") as r1:
                uids, scores = single_granularity_retrieve(self.store, fine, k)
            return RetrievalResult(uids=uids, scores=scores, filtered_uids=uids,
                                   n_refined=0, latency_s=r1.s,
                                   per_round_s={})
        return speculative_retrieve(
            self.store, [by_g[g] for g in self.granularities], fine,
            k=k, final_k=final_k, refine_fn=self.refine_fn,
            refine_budget=refine_budget, impl=self.search_impl,
            freshness=self.freshness, nprobe=self.nprobe)

    # -- batched queries -----------------------------------------------------

    def query_batch(self, queries, *, k: int = 10, final_k: int = 10,
                    refine_budget: Optional[int] = None,
                    speculative: bool = True) -> List[RetrievalResult]:
        """Serve a whole drain of queries at once (see module docstring).
        Per-result ``latency_s``/``per_round_s`` are the batch wall time
        amortized over the batch."""
        queries = np.stack([np.asarray(q) for q in queries])
        B = len(queries)
        if B == 0:
            return []
        with spans.span("query.query_batch"):
            spans.count("queries", B)
            if not speculative:
                return self._fine_batch(queries, k)
            return self._speculative_batch(queries, k, final_k,
                                           refine_budget)

    def _fine_batch(self, queries, k: int) -> List[RetrievalResult]:
        """``query_batch(speculative=False)``: the fine embedding alone."""
        B = len(queries)
        with spans.span("query.filter") as r1:
            fine_q = self.embed_query_batch(queries)[:, -1]
            uids, scores = self.store.search_batch(fine_q, k,
                                                   impl=self.search_impl,
                                                   freshness=self.freshness,
                                                   nprobe=self.nprobe)
        dt = r1.s / B
        # drop IVF padding slots (uid -1 / score -1e30): no exhaustive
        # path ever emits them, so callers must never see them here
        live = scores > -5e29
        return [RetrievalResult(uids=uids[b][live[b]],
                                scores=scores[b][live[b]],
                                filtered_uids=uids[b][live[b]],
                                n_refined=0,
                                latency_s=dt, per_round_s={})
                for b in range(B)]

    def _speculative_batch(self, queries, k: int, final_k: int,
                           refine_budget: Optional[int]
                           ) -> List[RetrievalResult]:
        B = len(queries)
        # round 1: every (query, granularity) pair in ONE fused store scan
        # (stale-tolerant under the async bank policy: rounds 2+3 verify and
        # re-score the candidates against live embeddings anyway)
        with spans.span("query.filter") as r1:
            QG = self.embed_query_batch(queries)        # (B, G, E)
            fine_q = QG[:, -1]                          # (B, E)
            G = QG.shape[1]
            flat_u, flat_s = self.store.search_batch(
                QG.reshape(B * G, -1), k, impl=self.search_impl,
                freshness=self.freshness, nprobe=self.nprobe)
            kk = flat_u.shape[1]
            u3 = flat_u.reshape(B, G, kk)
            s3 = flat_s.reshape(B, G, kk)

        # round 2: vectorized dedup per query; drop uids deleted since the
        # (possibly stale, under the async bank policy) scanned generation —
        # round 3 reads live store rows. ONE contains() call (= one store
        # lock acquisition) for the whole batch, sliced back per query.
        with spans.span("query.verify") as r2:
            cands = [global_verify(list(zip(u3[b], s3[b])), k)
                     for b in range(B)]
            lens = [u.size for u, _ in cands]
            if sum(lens):
                live_all = self.store.contains(
                    np.concatenate([u for u, _ in cands]))
                offs = np.cumsum([0] + lens)
                cands = [(u[live_all[o:o + n]], s[live_all[o:o + n]])
                         for (u, s), o, n in zip(cands, offs, lens)]

        # round 3: one deduplicated refinement batch across all queries
        # (shared retrieval.refine_round core; "attempts" = per-query budget
        # caps attempted candidates, no retry loop)
        with spans.span("query.refine") as r3:
            fine_per_q, n_ref_per_q = refine_round(
                self.store, [u for u, _ in cands], self.refine_fn,
                refine_budget, upgrade=True, budget_mode="attempts")

        with spans.span("query.match") as r4:
            ranked = []
            for b in range(B):
                uids_b, _ = cands[b]
                fine_embs = fine_per_q[b]
                n_ref = n_ref_per_q[b]
                if len(fine_embs):
                    scores = fine_embs @ fine_q[b]
                    order = np.argsort(-scores)[:final_k]
                    ranked.append((uids_b[order], scores[order], uids_b,
                                   n_ref))
                else:
                    ranked.append((np.zeros((0,), np.int64),
                                   np.zeros((0,), np.float32), uids_b, n_ref))
        per_round = {name: r.s / B for name, r in
                     (("filter", r1), ("verify", r2), ("refine", r3),
                      ("match", r4))}
        latency = sum(per_round.values())
        return [RetrievalResult(uids=u, scores=s, filtered_uids=fu,
                                n_refined=n, latency_s=latency,
                                per_round_s=dict(per_round))
                for u, s, fu, n in ranked]
