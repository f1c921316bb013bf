"""Query: back-to-back ``QueryEngine.query_batch`` drains of ``batch``
seeded text queries over a bank of ``bank_rows`` rows.

Each query of the run has ``placed_fresh`` fresh coarse rows (with seeded
cached vision activations) and ``placed_fine`` fine rows placed to be its
top ``placed_fresh + placed_fine``; every other row is a filler row at
``filler_norm``. Row j of query i is its placed direction scaled by
1 - 0.01 j, fresh rows first, so round 3 refines exactly the drain's own
fresh rows, each once for good. The fresh pool holds ``pool_drains``
drains; a run that outlasts it fails loudly. The text and vision towers'
references, controls and operation counts are their blocks' (``spec.block``).

Traffic keys: ``batch``, ``k``, ``bank_rows``, ``filler_norm``,
``placed_fine``, ``placed_fresh``, ``own_score`` and ``pool_drains`` (when
rows are placed), ``filler_check_rows``, ``warmup_drains``.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench import base, counts, data, gaps, spec
from reference import int4 as R4

MAX_PLACED = 16          # placed rows per query: uid PLACED_UID0 + 16 i + j


class Loop(base.Loop):
    SPAN = "query_batch"

    def service_kwargs(self, devs) -> dict:
        return {"search_devices": devs}

    def build(self, engine, query) -> None:
        self.engine, self.query, self.store = engine, query, engine.store
        tr, cfg = self.tr, self.cfg
        self.B, self.k = int(tr["batch"]), int(tr["k"])
        self.E = cfg["model"]["embed_dim"]
        self.tt = spec.tower(cfg, "text")
        self.vt = spec.tower(cfg, "vision")
        self.tblk, self.vblk = (spec.block(cfg, m,
                                           bench_dir=self.cell.bench_dir)
                                for m in ("text", "vision"))
        self.N = cfg["recall"]["superficial_layers"]
        self.grans = spec.query_granularities(cfg, self.tt["n_layers"])
        self.n_fine, self.n_fresh = int(tr["placed_fine"]), \
            int(tr["placed_fresh"])
        n_own = self.n_fine + self.n_fresh
        if self.n_fresh > self.k or n_own > MAX_PLACED:
            raise spec.SpecError(
                f"placed_fresh ({self.n_fresh}) must be at most k "
                f"({self.k}) and placed rows ({n_own}) at most {MAX_PLACED}")
        store = self.store
        full = self.vt["n_layers"]
        n_placed = 0
        self.margin = None
        if n_own:
            self.pool_drains = int(tr["pool_drains"])
            P = self.pool_drains * self.B
            with self.phase("embed pool queries"):
                q = np.concatenate([query.embed_query_batch(self.ids(d))
                                    for d in range(self.pool_drains)])
            with self.phase("place rows"):
                self.R = data.place_rows(q, float(tr["own_score"]))
                self.margin = data.placement_margin(q, self.R, n_own - 1,
                                                    self.k)
            print(f"placement margin: {self.margin:.4f} of the own score")
            if self.margin <= 0.1:
                raise spec.SpecError("placed rows do not stand clear of "
                                     "other queries' rows")
            n_placed = P * n_own
        else:
            self.pool_drains = None
        with self.phase("filler rows"):
            self.filler = data.Filler(self.seed,
                                      int(tr["bank_rows"]) - n_placed,
                                      self.E, float(tr["filler_norm"]))
            for t in range(-(-self.filler.n // data.FILLER_BLOCK)):
                u, rows = self.filler.tile(t)
                store.add_batch(u, rows, np.zeros(len(u)),
                                np.full(len(u), full), fine=True)
        if self.n_fine:
            i = np.repeat(np.arange(len(self.R)), self.n_fine)
            j = np.tile(np.arange(self.n_fresh, n_own), len(self.R))
            store.add_batch(data.PLACED_UID0 + MAX_PLACED * i + j,
                            self._placed(i, j), np.zeros(len(i)),
                            np.full(len(i), full), fine=True)
        if self.n_fresh:
            exits = spec.exit_layers(cfg, full)
            e_idx = next(n for n, e in enumerate(exits) if e > self.N)
            m = self.B * self.n_fresh
            for d in range(self.pool_drains):
                u = self._fresh_uids(d, d + 1)
                with self.phase("fresh activations"):
                    acts = self._acts(d)
                with self.phase("fresh rows"):
                    store.add_batch(
                        u, self.harness_rows(u), np.full(m, e_idx),
                        np.full(m, exits[e_idx]), modality="vision",
                        cached_hs=acts)
        if len(store) != int(tr["bank_rows"]):
            raise spec.SpecError(f"bank holds {len(store)} rows, not "
                                 f"{tr['bank_rows']}")
        self.drains: List[dict] = []
        self.taps.append(base.Tap(store, "search_batch", self._on_search))
        self.taps.append(base.Tap(store, "upgrade_batch", self._on_upgrade))

    # -- data ----------------------------------------------------------------

    def ids(self, d: int) -> np.ndarray:
        return data.query_ids(self.seed, d, self.B, self.tt["n_tokens"],
                              self.tt["vocab"])

    def _placed(self, i, j) -> np.ndarray:
        """Placed row j of query i."""
        return self.R[i] * (1.0 - 0.01 * np.asarray(j, np.float32))[:, None]

    def _fresh_uids(self, d0: int, d1: int) -> np.ndarray:
        """Fresh rows of drains [d0, d1): query i's row j is
        FRESH_UID0 + placed_fresh * i + j, so a drain's rows are a range."""
        m = self.B * self.n_fresh
        return data.FRESH_UID0 + np.arange(d0 * m, d1 * m)

    def _acts(self, d: int) -> np.ndarray:
        """Cached states of drain d's fresh rows, in uid order."""
        return data.fresh_activations(self.seed, d, self.B * self.n_fresh,
                                      self.vt["n_tokens"], self.vt["d_model"])

    def harness_rows(self, uids) -> np.ndarray:
        """The rows the harness inserted for ``uids`` (fresh rows as
        inserted, before any refinement), float32."""
        uids = np.asarray(uids, np.int64)
        out = np.zeros((len(uids), self.E), np.float32)
        f = uids < data.FRESH_UID0
        out[f] = self.filler.rows(uids[f])
        fr = (uids >= data.FRESH_UID0) & (uids < data.PLACED_UID0)
        if fr.any():
            off = uids[fr] - data.FRESH_UID0
            out[fr] = self._placed(off // self.n_fresh, off % self.n_fresh)
        pl = uids >= data.PLACED_UID0
        if pl.any():
            off = uids[pl] - data.PLACED_UID0
            out[pl] = self._placed(off // MAX_PLACED, off % MAX_PLACED)
        return out

    # -- window --------------------------------------------------------------

    def _on_search(self, queries, k, *, out=None, **kw):
        self.cur["search"] = (queries, out[0], out[1])

    def _on_upgrade(self, uids, embs, *, out=None, **kw):
        self.cur.setdefault("upgrades", []).append(
            (np.asarray(uids, np.int64), np.asarray(embs, np.float32)))

    def step(self) -> tuple:
        d = self.drain_no
        if self.pool_drains is not None and d >= self.pool_drains:
            raise RuntimeError(
                f"fresh pool exhausted after {d} drains: the traffic's "
                f"pool_drains ({self.pool_drains}) is too small for this "
                f"window; a benchmark change must raise it")
        self.cur = {"d": d}
        res = self.query.query_batch(self.ids(d), k=self.k)
        self.drain_no += 1
        up = self.cur.get("upgrades", [])
        got = np.concatenate([u for u, _ in up]) if up else np.zeros(0)
        n_ref = sum(r.n_refined for r in res)
        want = self._fresh_uids(d, d + 1)
        ok = (n_ref == len(want) and len(got) == len(want) and
              np.array_equal(np.sort(got), want))
        # keep arrays, not the results' Python objects: thousands of
        # objects held across the window would make the collector's full
        # passes stall drains
        self.cur.update(results=_Packed(res, self.k), ok=ok,
                        n_refined=int(n_ref),
                        refined_unique=int(len(np.unique(got))))
        if self.window_from is not None:
            self.drains.append(self.cur)
        return self.B, ok

    def report(self) -> None:
        for dc in self.drains:
            print(f"refine check, drain {dc['d']}: n_refined "
                  f"{dc['n_refined']}, unique {dc['refined_unique']}, "
                  f"{'ok' if dc['ok'] else 'MISSED'}")

    def collect(self) -> None:
        """Choose the checked drain and take from the store what the checks
        need: the stored rows as they stood when that drain scanned, and
        the exact checks of stored rows against the int4 rule."""
        r = data.rng(self.seed, 7)
        self.dc = self.drains[int(r.integers(len(self.drains)))]
        d = self.dc["d"]
        store = self.store
        dense = store.dense_matrix()            # dequantized stored rows
        self.bank_uids = store.uids()
        self.dense = dense
        patch_rows, patch_vals = np.zeros(0, np.int64), np.zeros((0, self.E))
        bad = 0
        if self.n_fresh:
            # fresh rows refined in this drain or later were still coarse
            # when this drain scanned: put their inserted values back
            later = self._fresh_uids(d, self.drain_no)
            patch_rows = store.rows_of(later)
            patch_vals = R4.roundtrip(self.harness_rows(later))
            up = self.dc.get("upgrades", [])
            self.up_uids = np.concatenate([np.zeros(0, np.int64)] +
                                          [u for u, _ in up])
            self.up_embs = np.concatenate([np.zeros((0, self.E),
                                                    np.float32)] +
                                          [e for _, e in up])
            bad += gaps.mismatch(store.get_embeddings(self.up_uids),
                                 R4.roundtrip(self.up_embs))
        self.patch = dict(zip(patch_rows.tolist(), patch_vals))
        # stored rows the harness inserted: a seeded sample of filler rows,
        # and every fine row placed for the checked drain's queries
        n = min(int(self.tr["filler_check_rows"]), self.filler.n)
        check = data.FILLER_UID0 + np.sort(r.choice(self.filler.n, n,
                                                    replace=False))
        if self.n_fine:
            i = np.repeat(np.arange(d * self.B, (d + 1) * self.B), self.n_fine)
            j = np.tile(np.arange(self.n_fresh, self.n_fresh + self.n_fine),
                        self.B)
            check = np.concatenate([check,
                                    data.PLACED_UID0 + MAX_PLACED * i + j])
        bad += gaps.mismatch(store.get_embeddings(check),
                             R4.roundtrip(self.harness_rows(check)))
        self.readings["store_mismatch"] = float(bad)

    def _bank_row(self, rows: np.ndarray) -> np.ndarray:
        out = self.dense[rows].astype(np.float32)
        for n, r in enumerate(rows.tolist()):
            if r in self.patch:
                out[n] = self.patch[r]
        return out

    def check(self) -> None:
        dc, B, G, k = self.dc, self.B, len(self.grans), self.k
        d = dc["d"]
        qs, pu, ps = dc["search"]
        qs = np.asarray(qs, np.float32)
        # query tower: every granularity of every query in the drain
        text = self.tblk.Tower(self.cfg, self.seed, "text")
        q_ref = np.zeros((B, G, self.E), np.float32)
        for lo in range(0, B, 16):
            embs, _ = text.run(inputs=self.ids(d)[lo:lo + 16],
                               exits=tuple(self.grans))
            for g, e in enumerate(self.grans):
                q_ref[lo:lo + 16, g] = embs[e]
        del text
        self.readings["query_gap"] = gaps.emb_gap(
            qs.reshape(B, G, self.E), q_ref)
        # round 1: the device scan against an exact numpy scan of the rows
        # the store held then, for the queries the scan was given
        rows, rs = R4.scan_topk(qs, self.dense, k, patch=self.patch)
        true = np.einsum("qe,qke->qk", qs, self._bank_row_of_uids(pu))
        self.readings["scan_gap"] = gaps.scan_gap(rs, true, ps)
        # round 2: the program's verify against the plain one
        res = dc["results"]
        u3 = np.asarray(pu).reshape(B, G, -1)
        s3 = np.asarray(ps).reshape(B, G, -1)
        self.readings["verify_mismatch"] = float(sum(
            not np.array_equal(R4.verify(u3[b], s3[b], k),
                               res.filtered(b)) for b in range(B)))
        # round 3: refined fresh rows, continued from the cached state
        refined = {}
        if self.n_fresh:
            vis = self.vblk.Tower(self.cfg, self.seed, "vision")
            h = R4.roundtrip(self._acts(d).astype(np.float32))
            e_ref = np.zeros((len(h), self.E), np.float32)
            for lo in range(0, len(h), 16):
                embs, _ = vis.run(h_state=h[lo:lo + 16], start=self.N,
                                  exits=(self.vt["n_layers"],))
                e_ref[lo:lo + 16] = embs[self.vt["n_layers"]]
            del vis
            fresh = self._fresh_uids(d, d + 1)
            refined = dict(zip(fresh.tolist(), e_ref))
            pos = {u: n for n, u in enumerate(self.up_uids.tolist())}
            self.readings["refine_gap"] = gaps.emb_gap(
                [self.up_embs[pos[u]] for u in fresh.tolist()], e_ref) \
                if set(pos) >= set(refined) else float("inf")
        # the final ranking over each query's verified candidates
        worst = 0.0
        for b in range(B):
            cand = res.filtered(b)
            vecs = R4.roundtrip(self.harness_rows(cand))
            for n, u in enumerate(cand.tolist()):
                if u in refined:
                    vecs[n] = refined[u]
            s_ref = dict(zip(cand.tolist(), vecs @ q_ref[b, -1]))
            uids, scores = res.final(b)
            worst = max(worst, gaps.rank_gap(uids, scores, s_ref, k))
        self.readings["rank_gap"] = worst

    def _bank_row_of_uids(self, uids) -> np.ndarray:
        pos = self.uid_pos
        rows = np.array([pos[int(u)] for u in np.asarray(uids).ravel()])
        return self._bank_row(rows).reshape(np.asarray(uids).shape +
                                            (self.E,))

    @property
    def uid_pos(self) -> Dict[int, int]:
        if not hasattr(self, "_uid_pos"):
            self._uid_pos = {int(u): n for n, u in
                             enumerate(self.bank_uids.tolist())}
        return self._uid_pos

    def work(self, window_s: float, peaks: dict) -> dict:
        """Model work per window: query tower, refine continuation, scan."""
        cfg, B, G = self.cfg, self.B, len(self.grans)
        n = len(self.drains)
        tower, refine = self.flops(
            self.cell, n * B,
            sum(dc["refined_unique"] for dc in self.drains))
        cap = counts.bank_capacity(int(self.tr["bank_rows"]))
        scan_ops, scan_bytes = counts.scan_work(B * G, cap, cfg["model"]
                                                ["embed_dim"])
        scan = n * scan_ops
        busy = ((tower + refine) / peaks["bf16_flops"] +
                scan / peaks["int8_ops"])
        per_round = {key: 1e3 * B * float(np.mean(
            [dc["results"].per_round_s[key] for dc in self.drains]))
            for key in ("filter", "verify", "refine", "match")}
        return {"mfu": busy / window_s, "per_round_ms": per_round,
                "scan_calls": n, "scan_ops": scan_ops,
                "scan_bytes": scan_bytes, "bank_capacity": cap,
                "embed_dim": cfg["model"]["embed_dim"]}

    @staticmethod
    def flops(cell, queries: int, refined: int) -> tuple:
        """(FLOPs of the query tower for ``queries`` queries, all of its
        layers and an exit head at each exit; FLOPs of continuing
        ``refined`` cached vision states from layer N through the last
        layer and the exit head)."""
        cfg = cell.config
        tt, vt = spec.tower(cfg, "text"), spec.tower(cfg, "vision")
        tb, vb = (spec.block(cfg, m, bench_dir=cell.bench_dir)
                  for m in ("text", "vision"))
        N = cfg["recall"]["superficial_layers"]
        tower = queries * (tb.layer_flops(tt, cfg, 0, tt["n_layers"]) +
                           len(spec.exit_layers(cfg, tt["n_layers"])) *
                           tb.exit_head_flops(tt, cfg))
        refine = refined * (vb.layer_flops(vt, cfg, N, vt["n_layers"]) +
                            vb.exit_head_flops(vt, cfg))
        return tower, refine

    @staticmethod
    def control(cell, seed: int) -> dict:
        return _control(cell, seed)


class _Packed:
    """One drain's results as a few arrays: final (uids, scores) and the
    verified candidates of each query, padded with -1, and the per-round
    times (the same for every query of a drain)."""

    def __init__(self, res, k: int):
        B = len(res)
        self.uids = np.full((B, k), -1, np.int64)
        self.scores = np.zeros((B, k), np.float32)
        self.cand = np.full((B, k), -1, np.int64)
        self.n, self.nc = np.zeros(B, np.int64), np.zeros(B, np.int64)
        for b, r in enumerate(res):
            n, nc = len(r.uids), len(r.filtered_uids)
            self.uids[b, :n], self.scores[b, :n] = r.uids, r.scores
            self.cand[b, :nc] = r.filtered_uids
            self.n[b], self.nc[b] = n, nc
        self.per_round_s = dict(res[0].per_round_s)

    def final(self, b: int):
        return self.uids[b, :self.n[b]], self.scores[b, :self.n[b]]

    def filtered(self, b: int) -> np.ndarray:
        return self.cand[b, :self.nc[b]]


def _int8_rows(x: np.ndarray) -> np.ndarray:
    s = np.maximum(np.abs(x).max(axis=1, keepdims=True), 1e-30) / 127.0
    return (np.clip(np.rint(x / s), -127, 127) * s).astype(np.float32)


def _control(cell, seed: int) -> dict:
    """The numbers a run compares, for the reference one precision lower:
    tower matmuls at float8, the scan's queries at int8, on the first
    window drain's queries and cached states over the bank that drain
    scans (filler rows and the drain's own placed rows)."""
    cfg, tr = cell.config, cell.traffic
    B, k = int(tr["batch"]), int(tr["k"])
    E = cfg["model"]["embed_dim"]
    tt = spec.tower(cfg, "text")
    grans = spec.query_granularities(cfg, tt["n_layers"])
    d = int(tr["warmup_drains"])                   # first window drain
    ids = data.query_ids(seed, d, B, tt["n_tokens"], tt["vocab"])
    tblk = spec.block(cfg, "text", bench_dir=cell.bench_dir)
    towers = {"ref": tblk.Tower(cfg, seed, "text"),
              "low": tblk.Tower(cfg, seed, "text", cast="fp8")}
    qs = {}
    for name, tower in towers.items():
        q = np.zeros((B, len(grans), E), np.float32)
        for lo in range(0, B, 16):
            embs, _ = tower.run(inputs=ids[lo:lo + 16], exits=tuple(grans))
            for g, e in enumerate(grans):
                q[lo:lo + 16, g] = embs[e]
        qs[name] = q
    out = {"query_gap": gaps.emb_gap(qs["low"], qs["ref"])}
    n_fine, n_fresh = int(tr["placed_fine"]), int(tr["placed_fresh"])
    n_own = n_fine + n_fresh
    n_placed = n_own * B * int(tr.get("pool_drains", 0))
    filler = data.Filler(seed, int(tr["bank_rows"]) - n_placed, E,
                         float(tr["filler_norm"]))
    rows = [R4.roundtrip(filler.tile(t)[1])
            for t in range(-(-filler.n // data.FILLER_BLOCK))]
    base_row = sum(len(r) for r in rows)
    if n_own:
        R = data.place_rows(qs["ref"], float(tr["own_score"]))
        own = [R4.roundtrip(R * (1 - 0.01 * j))[:, None]
               for j in range(n_own)]
        rows.append(np.concatenate(own, axis=1).reshape(-1, E))
    bank = np.concatenate(rows)
    refined = {}
    if n_fresh:
        vt = spec.tower(cfg, "vision")
        N = cfg["recall"]["superficial_layers"]
        h = R4.roundtrip(data.fresh_activations(
            seed, d, B * n_fresh, vt["n_tokens"],
            vt["d_model"]).astype(np.float32))
        es = {}
        vblk = spec.block(cfg, "vision", bench_dir=cell.bench_dir)
        for name, cast in (("ref", "f32"), ("low", "fp8")):
            tower = vblk.Tower(cfg, seed, "vision", cast=cast)
            e = np.zeros((len(h), E), np.float32)
            for lo in range(0, len(h), 16):
                embs, _ = tower.run(h_state=h[lo:lo + 16], start=N,
                                    exits=(vt["n_layers"],))
                e[lo:lo + 16] = embs[vt["n_layers"]]
            es[name] = e
        out["refine_gap"] = gaps.emb_gap(es["low"], es["ref"])
        refined = {base_row + b * n_own + j: (es["ref"][b * n_fresh + j],
                                              es["low"][b * n_fresh + j])
                   for b in range(B) for j in range(n_fresh)}
    # round 1: the scan with its queries rounded to int8
    flat = qs["ref"].reshape(-1, E)
    best_i, best_s = R4.scan_topk(flat, bank, k)
    low_i, low_s = R4.scan_topk(_int8_rows(flat), bank, k)
    true = np.einsum("qe,qke->qk", flat, bank[low_i])
    low_true_s = np.einsum("qe,qke->qk", _int8_rows(flat), bank[low_i])
    out["scan_gap"] = gaps.scan_gap(best_s, true, low_true_s)
    # final ranking over the reference's verified candidates
    worst = 0.0
    G = len(grans)
    bi = best_i.reshape(B, G, k)
    bs = best_s.reshape(B, G, k)
    for b in range(B):
        cand = R4.verify(bi[b], bs[b], k)
        v_ref, v_low = bank[cand].copy(), bank[cand].copy()
        for n, r in enumerate(cand.tolist()):
            if r in refined:
                v_ref[n], v_low[n] = refined[r]
        s_ref = dict(zip(cand.tolist(), v_ref @ qs["ref"][b, -1]))
        s_low = v_low @ qs["low"][b, -1]
        order = np.argsort(-s_low, kind="stable")[:k]
        worst = max(worst, gaps.rank_gap(cand[order], s_low[order], s_ref,
                                         k))
    out["rank_gap"] = worst
    return out
