"""Whole runs of the harness on the CPU at smoke widths (``chipbench.smoke``),
with the chip look skipped: each loop comes out correct, the fresh mix
refines exactly each drain's own fresh rows and fails loudly when its pool
runs out, and the timed path broken underneath makes ``correct`` false.
The control (the reference at float8), judged by the run's own comparison
against the real cells' limits, comes out not correct. A new loop kind, a
new end-to-end metric and a new cell of an existing loop are added files
and entries only."""
import json

import numpy as np
import pytest

import run as RUN
from chipbench import smoke, spec

SEED = 2 ** 31 + 4242


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("chipbench")
    return root, smoke.make_tree(root)


def _run(tree, workload, seconds=0.2):
    root, bench = tree
    return RUN.run(["--workload", workload, "--seed", str(SEED),
                    "--seconds", str(seconds), "--trace", "0"],
                   root=root, bench_dir=bench, require_tpu=False,
                   peaks=smoke.CPU_PEAKS)


@pytest.mark.parametrize("workload", ["tiny.ingest", "tiny.fresh",
                                      "tiny.warm"])
def test_sound_run_is_correct(tree, workload):
    res = _run(tree, workload)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["compilations_in_window"] == 0
    names = set(res["metrics"])
    assert "setup_s" in names and len(names) >= 2
    assert all(v["value"] > 0 for v in res["metrics"].values())


def _refined_per_drain(tree, monkeypatch, workload):
    """Run ``workload``; the uids each drain upgraded, checking that every
    drain refined exactly as many rows as it upgraded, each once."""
    cls = spec.loop_class("query", bench_dir=tree[1])
    seen = []
    orig = cls.step

    def step(self):
        out = orig(self)
        seen.append(self.cur)
        return out
    monkeypatch.setattr(cls, "step", step)
    res = _run(tree, workload)
    assert res["correct"], res["checks"]
    ups = []
    for dc in seen:
        up = np.concatenate([u for u, _ in dc["upgrades"]])
        assert dc["ok"] and dc["n_refined"] == len(up) == dc["refined_unique"]
        ups.append(up)
    all_up = np.concatenate(ups)
    assert len(np.unique(all_up)) == len(all_up)       # no row twice
    return ups


def test_fresh_drains_refine_exactly_their_own_rows(tree, monkeypatch):
    B = smoke.TRAFFIC["fresh"]["batch"]
    ups = _refined_per_drain(tree, monkeypatch, "tiny.fresh")
    assert all(len(u) == B for u in ups)


def test_fresh_pool_exhaustion_fails_loudly(tree):
    root, bench = tree
    path = bench / "traffic" / "tiny-fresh.json"
    tr = json.loads(path.read_text())
    try:
        path.write_text(json.dumps(dict(tr, pool_drains=3)))
        with pytest.raises(RuntimeError, match="pool exhausted"):
            _run(tree, "tiny.fresh", seconds=5)
    finally:
        path.write_text(json.dumps(tr))


def _altered_continuation(monkeypatch):
    from repro.serving import engine as EN
    orig = EN.EmbeddingEngine._continue_fn

    def cont(self, start, end):
        fn = orig(self, start, end)
        return lambda p, lo, h: -fn(p, lo, h)
    monkeypatch.setattr(EN.EmbeddingEngine, "_continue_fn", cont)


def _half_batch(monkeypatch):
    from repro.serving import engine as EN
    orig = EN.EmbeddingEngine.drain

    def drain(self):
        del self._queue[len(self._queue) // 2:]
        return orig(self)
    monkeypatch.setattr(EN.EmbeddingEngine, "drain", drain)


def _unchanged_store(monkeypatch):
    from repro.serving import engine as EN

    def drain(self):
        self._queue.clear()
        return self.stats
    monkeypatch.setattr(EN.EmbeddingEngine, "drain", drain)


@pytest.mark.parametrize("fault", [_altered_continuation, _half_batch,
                                   _unchanged_store])
def test_broken_ingest_is_not_correct(tree, monkeypatch, fault):
    fault(monkeypatch)
    assert not _run(tree, "tiny.ingest")["correct"]


def _altered_answer(monkeypatch):
    from repro.serving import query as QE
    orig = QE.QueryEngine.query_batch

    def qb(self, *a, **kw):
        res = orig(self, *a, **kw)
        r = res[len(res) // 2]
        r.uids = np.roll(r.uids, 1)
        return res
    monkeypatch.setattr(QE.QueryEngine, "query_batch", qb)


def _no_upgrade(monkeypatch):
    from repro.serving import query as QE
    orig = QE.refine_round
    monkeypatch.setattr(QE, "refine_round",
                        lambda *a, **kw: orig(*a, **dict(kw, upgrade=False)))


def _altered_scan(monkeypatch):
    from repro.core import device_bank as DB
    orig = DB.DeviceBank.search

    def search(self, *a, **kw):
        idx, s = orig(self, *a, **kw)
        return idx[:, ::-1].copy(), s
    monkeypatch.setattr(DB.DeviceBank, "search", search)


def _altered_tower(monkeypatch):
    from repro.serving import query as QE
    orig = QE.QueryEngine.embed_query_batch

    def emb(self, q):
        out = orig(self, q)
        out[0, 0] = -out[0, 0]
        return out
    monkeypatch.setattr(QE.QueryEngine, "embed_query_batch", emb)


@pytest.mark.parametrize("workload,fault", [
    ("tiny.fresh", _altered_answer), ("tiny.fresh", _no_upgrade),
    ("tiny.fresh", _altered_scan), ("tiny.fresh", _altered_tower),
    ("tiny.warm", _altered_answer), ("tiny.warm", _altered_scan),
    ("tiny.warm", _altered_tower)])
def test_broken_query_path_is_not_correct(tree, monkeypatch, workload, fault):
    fault(monkeypatch)
    assert not _run(tree, workload)["correct"]


@pytest.mark.parametrize("workload", ["tiny.ingest", "tiny.fresh",
                                      "tiny.warm"])
def test_control_fails_the_cells_limits(tree, workload):
    """The tiny cells carry the real cells' limits files (``smoke``)."""
    import control
    root, bench = tree
    cell = spec.load_cell(workload, root=root, bench_dir=bench)
    assert cell.limits == spec.load_cell(
        smoke.REAL[workload.split(".")[1]]).limits
    res = control.judge(cell, SEED)
    assert res["correct"] is False, res["checks"]
    assert any(v > lim for v, lim in res["checks"].values())


def _add_files(tree, files: dict, entries: dict):
    """Write new files under the bench dir and append entries to
    BENCHMARK.json; returns the bytes of every file that was there."""
    root, bench = tree
    before = {p: p.read_bytes() for p in bench.rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    for rel, text in files.items():
        assert not (bench / rel).exists(), rel
        (bench / rel).write_text(text)
    b = json.loads((root / "BENCHMARK.json").read_text())
    for key, new in entries.items():
        b[key] = b[key] + new
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return before


def _unchanged(before):
    for p, data in before.items():
        assert p.read_bytes() == data, p


DUMMY_LOOP = '''
"""A new kind of traffic: drains of the query tower alone."""
import numpy as np

from chipbench import base, data, gaps, spec
from reference import tower as RT


class Loop(base.Loop):
    SPAN = "embed_query_batch"

    def build(self, engine, query):
        self.query, self.B = query, int(self.tr["batch"])
        self.tt = spec.tower(self.cfg, "text")
        self.grans = spec.query_granularities(self.cfg, self.tt["n_layers"])

    def ids(self, d):
        return data.query_ids(self.seed, d, self.B, self.tt["n_tokens"],
                              self.tt["vocab"])

    def step(self):
        self.last = (self.drain_no, self.query.embed_query_batch(
            self.ids(self.drain_no)))
        self.drain_no += 1
        return self.B, True

    def collect(self):
        pass

    def check(self):
        d, got = self.last
        embs, _ = RT.Tower(self.cfg, self.seed, "text").run(
            inputs=self.ids(d), exits=tuple(self.grans))
        ref = np.stack([embs[e] for e in self.grans], axis=1)
        self.readings["query_gap"] = gaps.emb_gap(got, ref)

    def work(self, window_s, peaks):
        return {}
'''


def test_a_new_loop_and_end_to_end_metric_are_added_files_only(tree):
    before = _add_files(tree, {
        "loops/towers.py": DUMMY_LOOP,
        "traffic/tiny-towers.json": json.dumps(
            {"loop": "towers", "batch": 4, "warmup_drains": 2}),
        "limits/tiny.towers.json": json.dumps(
            {"numbers": {"query_gap": {"limit": 0.05}}}),
        "end_to_end/embeds_per_s.py":
            "def read(ctx):\n"
            "    if ctx['loop'] != 'towers':\n"
            "        return None\n"
            "    return ctx['units'] / ctx['window_s']\n"},
        {"workloads": [{"name": "tiny.towers", "config": "tiny",
                        "traffic": "tiny-towers", "chips": 1,
                        "why": "test"}],
         "end_to_end": [{"name": "embeds_per_s", "unit": "queries/s",
                         "better": "higher", "bound": 0.05,
                         "source": "host_clock",
                         "workloads": ["tiny.towers"]}]})
    res = _run(tree, "tiny.towers")
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"embeds_per_s", "setup_s"}
    assert res["metrics"]["embeds_per_s"]["value"] > 0
    _unchanged(before)


def test_a_cell_with_two_fresh_rows_per_query_is_data_only(tree,
                                                           monkeypatch):
    tr = dict(smoke.TRAFFIC["fresh"], placed_fresh=2, placed_fine=8,
              pool_drains=30)
    before = _add_files(tree, {
        "traffic/tiny-fresh2.json": json.dumps(tr),
        "limits/tiny.fresh2.json": (
            tree[1] / "limits" / "tiny.fresh.json").read_text()},
        {"workloads": [{"name": "tiny.fresh2", "config": "tiny",
                        "traffic": "tiny-fresh2", "chips": 1,
                        "why": "test"}]})
    ups = _refined_per_drain(tree, monkeypatch, "tiny.fresh2")
    assert all(len(u) == 2 * tr["batch"] for u in ups)
    _unchanged(before)
