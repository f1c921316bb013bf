"""Ingest: back-to-back ``EmbeddingEngine.drain`` calls, each of
``items_per_drain`` seeded items of the traffic's modality, every item
exiting at ``exit_layer`` (``policy="fixed"``), so the continuation has one
shape. The tower's reference, control and operation counts are its block's
(``spec.block``).

Traffic keys: ``modality`` (default ``"vision"``), ``items_per_drain``,
``item_pool`` (distinct seeded items the drains draw from), ``exit_layer``,
``kept_per_chunk`` (items of each engine chunk whose stored row and cached
state the check keeps), ``check_items`` (of those, how many go through the
reference tower), ``warmup_drains``.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench import base, data, gaps, spec
from reference import int4 as R4


class Loop(base.Loop):
    UID0 = 5 * 10 ** 9
    SPAN = "drain"

    def build(self, engine, query) -> None:
        tr = self.tr
        self.mod = _modality(tr)
        if engine.modality != self.mod:
            engine = _engine_for(engine, self.mod)
        self.engine, self.store = engine, engine.store
        self.t = spec.tower(self.cfg, self.mod)
        self.block = spec.block(self.cfg, self.mod,
                                bench_dir=self.cell.bench_dir)
        self.N = self.cfg["recall"]["superficial_layers"]
        self.exit = int(tr["exit_layer"])
        exits = spec.exit_layers(self.cfg, self.t["n_layers"])
        if self.exit not in exits or self.exit <= self.N:
            raise spec.SpecError(f"exit {self.exit} is not an exit past the "
                                 f"superficial prefix ({exits}, N={self.N})")
        # every item exits at one depth: one continuation shape per chunk
        engine.policy, engine.fixed_exit = "fixed", self.exit
        self.per = int(tr["items_per_drain"])
        with self.phase("items"):
            self.pool = data.ingest_pool(self.seed, int(tr["item_pool"]),
                                         self.t)
        self.order = data.rng(self.seed, 5)
        # kept items: seeded slots in each engine chunk of every window drain
        chunk, kpc = engine.max_batch, int(tr["kept_per_chunk"])
        r = data.rng(self.seed, 6)
        self.slots = np.sort(np.concatenate([
            c + r.choice(min(chunk, self.per - c), kpc, replace=False)
            for c in range(0, self.per, chunk)]))
        self.want: Dict[int, int] = {}       # uid -> pool index, this drain
        self.kept: Dict[int, tuple] = {}     # uid -> (pool idx, emb, h)
        self.drain_ok: List[bool] = []
        self.taps.append(base.Tap(self.store, "add_batch", self._on_add))

    def _on_add(self, uids, embs, exit_idxs, exit_layers, *, out=None,
                cached_hs=None, **kw):
        if not self.want:
            return
        uids = np.asarray(uids)
        for j in np.nonzero(np.isin(uids, list(self.want)))[0]:
            u = int(uids[j])
            self.kept[u] = (self.want[u], np.array(embs[j], np.float32),
                            None if cached_hs is None
                            else np.array(cached_hs[j]),
                            int(np.asarray(exit_layers)[j]))

    def step(self) -> tuple:
        d = self.drain_no
        idx = self.order.choice(len(self.pool), self.per, replace=False)
        uids = self.UID0 + d * self.per + np.arange(self.per)
        if self.window_from is not None:
            self.want = {int(uids[s]): int(idx[s]) for s in self.slots}
        n0 = len(self.store)
        self.engine.submit_batch(uids, [self.pool[i] for i in idx])
        self.engine.drain()
        self.drain_no += 1
        ok = len(self.store) - n0 == self.per
        if self.window_from is not None:
            self.drain_ok.append(ok)
        return self.per, ok

    def _stats(self) -> dict:
        st = self.engine.stats
        return {"layers": st.layers_executed, "items": st.n_embedded}

    def collect(self) -> None:
        """Store-side checks, before the program's state is freed: the
        stored embedding and cached state of every kept item are exactly
        the int4 rule applied to what the tower produced for it."""
        uids = np.array(sorted(self.kept), np.int64)
        expect = set()
        for d in range(self.window_from, self.drain_no):
            expect |= {int(self.UID0 + d * self.per + s) for s in self.slots}
        missing = len(expect - set(self.kept))
        present = self.store.contains(uids)
        bad = int(np.sum(~present)) + missing
        uids = uids[present]
        if len(uids):
            emb = self.store.get_embeddings(uids)
            bad += gaps.mismatch(emb, R4.roundtrip(
                np.stack([self.kept[int(u)][1] for u in uids])))
            acts = self.store.cached_activations(uids)
            for u in uids.tolist():
                _, _, h, layer = self.kept[u]
                got = acts.get(u)
                if (got is None or layer != self.exit or
                        not np.array_equal(got[0], R4.roundtrip(
                            h.astype(np.float32)))):
                    bad += 1
        self.readings["store_mismatch"] = float(bad)
        r = data.rng(self.seed, 7)
        n = min(int(self.tr["check_items"]), len(uids))
        self.check_uids = np.sort(r.choice(uids, n, replace=False))

    def check(self) -> None:
        """The tower against its block's reference, on the checked items:
        the cached superficial state (layer N) and the exit embedding."""
        ref = self.block.Tower(self.cfg, self.seed, self.mod)
        gaps_e, gaps_h = [], []
        for lo in range(0, len(self.check_uids), 16):
            us = self.check_uids[lo:lo + 16].tolist()
            x = self.pool[[self.kept[u][0] for u in us]]
            embs, h = ref.run(inputs=x, end=self.exit, exits=(self.exit,),
                              keep_h_at=self.N)
            gaps_e.append(gaps.emb_gap([self.kept[u][1] for u in us],
                                       embs[self.exit]))
            gaps_h.append(gaps.rel_gap(np.stack(
                [self.kept[u][2] for u in us]).astype(np.float32), h))
        self.readings["emb_gap"] = max(gaps_e) if gaps_e else float("inf")
        self.readings["sup_gap"] = max(gaps_h) if gaps_h else float("inf")

    def work(self, window_s: float, peaks: dict) -> dict:
        flops = self.flops(self.cell,
                           self.stats1["items"] - self.stats0["items"],
                           self.stats1["layers"] - self.stats0["layers"])
        return {"flops": flops, "mfu": flops / window_s / peaks["bf16_flops"]}

    @staticmethod
    def flops(cell, items: float, layers: float) -> float:
        """FLOPs of ``items`` items over ``layers`` tower layers run in all
        (``EngineStats``): each layer at the mean of the layers [0, exit)
        that every item runs, plus each item's frontend and exit head."""
        cfg, mod = cell.config, _modality(cell.traffic)
        t = spec.tower(cfg, mod)
        blk = spec.block(cfg, mod, bench_dir=cell.bench_dir)
        ex = int(cell.traffic["exit_layer"])
        return (layers * (blk.layer_flops(t, cfg, 0, ex) / ex) +
                items * blk.frontend_flops(t, cfg) +
                items * blk.exit_head_flops(t, cfg))

    @staticmethod
    def control(cell, seed: int) -> dict:
        """``emb_gap`` and ``sup_gap`` of the float8 tower against the
        float32 one, on ``check_items`` seeded items of the pool."""
        cfg, tr = cell.config, cell.traffic
        mod = _modality(tr)
        blk = spec.block(cfg, mod, bench_dir=cell.bench_dir)
        N, ex = cfg["recall"]["superficial_layers"], int(tr["exit_layer"])
        pool = data.ingest_pool(seed, int(tr["item_pool"]),
                                spec.tower(cfg, mod))
        pick = np.sort(data.rng(seed, 7).choice(
            len(pool), int(tr["check_items"]), replace=False))
        ref, low = blk.Tower(cfg, seed, mod), \
            blk.Tower(cfg, seed, mod, cast="fp8")
        ge, gh = [], []
        for lo in range(0, len(pick), 16):
            x = pool[pick[lo:lo + 16]]
            e32, h32 = ref.run(inputs=x, end=ex, exits=(ex,), keep_h_at=N)
            eL, hL = low.run(inputs=x, end=ex, exits=(ex,), keep_h_at=N)
            ge.append(gaps.emb_gap(eL[ex], e32[ex]))
            gh.append(gaps.rel_gap(hL, h32))
        return {"emb_gap": max(ge), "sup_gap": max(gh)}


def _modality(traffic: dict) -> str:
    return traffic.get("modality", "vision")


def _engine_for(engine, modality: str):
    """The program's engine for ``modality`` over the served weights and
    store: ``build_service`` builds one for vision, and the fixed policy
    consults no pre-exit predictor."""
    from repro.serving.engine import EmbeddingEngine
    return EmbeddingEngine(engine.params, engine.cfg, engine.recall,
                           modality=modality, lora=engine.lora,
                           max_batch=engine.max_batch, store=engine.store,
                           cache_activations=engine.cache_activations,
                           fw_kw=engine.fw_kw)
