"""The per-layer metrics that read the program's own spans
(``chipbench.program_spans``): a smoke-width drain under the profiler puts
the program's spans on the host events the trace reduction reads, and each
reader turns a recorded ``spans.window()`` into its per-drain number, and
reads nothing in an untraced run, for the other loop, or from a program
without the recorder."""
import builtins
import json

import jax
import numpy as np
import pytest

from chipbench import program_spans as PS
from chipbench import spec
from chipbench import trace as TR
from repro.configs.base import MEMConfig, RecallConfig, TowerConfig
from repro.core import spans
from repro.data.synthetic import multimodal_pairs
from repro.models import imagebind as IB
from repro.serving.engine import EmbeddingEngine
from repro.serving.query import QueryEngine

CFG = MEMConfig(towers=(TowerConfig("vision", 4, 32, 2, 64, 12, 16),
                        TowerConfig("text", 3, 32, 2, 64, 8, 0, vocab=128)),
                embed_dim=32)
RC = RecallConfig(exit_interval=1, superficial_layers=2, predictor_hidden=32,
                  lora_rank=4, query_granularities=2)
FW = dict(block_q=8, block_kv=8)

# metric -> (loop, what it reads from the window, per drain)
NEW = {
    "ingest.tower_ms": ("ingest", lambda w: 1e3 * (
        w["engine.superficial"]["s"] + w["engine.continue"]["s"])),
    "ingest.store_ms": ("ingest", lambda w: 1e3 * w["store.add_batch"]["s"]),
    "ingest.crossing_mb": ("ingest", lambda w: (
        w["engine.drain"]["h2d_bytes"] + w["engine.drain"]["d2h_bytes"]) / 1e6),
    "query.embed_ms": ("query", lambda w: 1e3 * w["query.embed"]["s"]),
    "query.scan_ms": ("query", lambda w: 1e3 * w["store.search_batch"]["s"]),
    "query.refine_continue_ms.fresh": ("query", lambda w: 1e3 * w[
        "engine.refine_continue"]["s"]),
    "query.refine_crossing_mb.fresh": ("query", lambda w: (
        w["query.refine"]["h2d_bytes"] + w["query.refine"]["d2h_bytes"])
        / 1e6),
}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Windows of two ingest drains and of two query drains, each recorded
    under a profiler session, and the trace of the ingest session."""
    params = IB.mem_init(jax.random.PRNGKey(0), CFG, RC)
    data = multimodal_pairs(0, 64, CFG)
    eng = EmbeddingEngine(params, CFG, RC, modality="vision", policy="fixed",
                          fixed_exit=4, max_batch=16, fw_kw=FW)
    q = QueryEngine(params, CFG, RC, store=eng.store,
                    refine_fn=eng.refine_fn(), query_modality="text",
                    fw_kw=FW, search_impl="device")
    vis, txt = data.items["vision"], data.items["text"]
    eng.submit_batch(np.arange(32), vis[:32])
    eng.drain()                                  # compile before the session
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(trace_dir):
        for d in range(2):
            eng.submit_batch(100 + 32 * d + np.arange(32), vis[:32])
            eng.drain()
    ingest = spans.window()
    q.query_batch(txt[:4], k=8)                  # compile before the session
    with jax.profiler.trace(str(tmp_path_factory.mktemp("trace"))):
        q.query_batch(txt[4:8], k=8)
        q.query_batch(txt[8:12], k=8)
    query = spans.window()
    return {"ingest": ingest, "query": query,
            "trace": TR.from_xplane(trace_dir)}


def test_program_spans_land_on_the_host_events_of_the_trace(recorded):
    names = {h[0] for h in recorded["trace"]["host"]}
    assert {"engine.drain", "engine.superficial", "engine.continue",
            "store.add_batch", "store.quantize"} <= names
    drains = [h for h in recorded["trace"]["host"] if h[0] == "engine.drain"]
    assert len(drains) == 2
    # the host events carry the span's own clock: the trace's drain
    # durations add up to the recorded seconds
    assert sum(d for _, _, d in drains) * 1e-9 == pytest.approx(
        recorded["ingest"]["engine.drain"]["s"], rel=0.05)


@pytest.mark.parametrize("metric", sorted(NEW))
def test_reader_gives_the_per_drain_reading(recorded, metric, monkeypatch):
    loop, expect = NEW[metric]
    win = recorded[loop]
    monkeypatch.setattr(spans, "window", lambda: json.loads(json.dumps(win)))
    read = spec.metric_reader(metric)
    drains = win[PS.ROOTS[loop]]["n"]
    assert drains == 2
    got = read({"trace": {}, "loop": loop})
    assert got == pytest.approx(expect(win) / drains)
    assert got > 0
    assert read({"trace": None, "loop": loop}) is None
    other = "query" if loop == "ingest" else "ingest"
    assert read({"trace": {}, "loop": other}) is None
    # a program without the recorder: nothing to read, nothing raised
    real_import = builtins.__import__

    def no_spans(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "repro.core" and fromlist and "spans" in fromlist:
            raise ImportError("no repro.core.spans")
        return real_import(name, globals, locals, fromlist, level)
    monkeypatch.setattr(builtins, "__import__", no_spans)
    assert read({"trace": {}, "loop": loop}) is None


def test_crossings_match_the_shapes(recorded):
    """Per ingest drain of 32 items in chunks of 16 (every item exiting at
    layer 4, past the 2-layer superficial prefix): the items up, the
    superficial state and the per-layer pooled states down, the state up
    again, the embeddings down, and the store's int4 codes and scales of
    the cached states down (the store quantizes them on the device: d/2
    bytes of nibbles and one float32 scale a token row)."""
    v = CFG.tower("vision")
    S, d, E, N = v.n_tokens + 1, v.d_model, CFG.embed_dim, 2
    w = recorded["ingest"]
    isz = np.dtype(CFG.dtype).itemsize           # the towers' activations
    items = 32 * v.n_tokens * v.d_input * 4
    want = items + 32 * S * d * isz + N * 32 * d * isz + 32 * S * d * isz \
        + 32 * E * 4 + 32 * S * (d // 2 + 4)
    assert (w["engine.drain"]["h2d_bytes"] + w["engine.drain"]["d2h_bytes"]
            ) / w["engine.drain"]["n"] == want
