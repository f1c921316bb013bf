"""The span recorder (``repro.core.spans``) and its spans in the served path:
nesting and self time, counters inclusive of enclosing spans, sessions,
nothing kept outside a profiler session, one stack per thread; the query
rounds' ``per_round_s`` read from the round spans; the byte counters equal
the bytes of the arrays that cross, in a drain and in a refine."""
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs.base import MEMConfig, RecallConfig, TowerConfig
from repro.core import spans
from repro.core import store as store_mod
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


@pytest.fixture
def collecting(monkeypatch):
    """Stand in for a profiler session: ``state["on"]`` is what the
    recorder sees as the profiler's collecting flag."""
    state = {"on": True}
    monkeypatch.setattr(spans, "_collecting", lambda: state["on"])
    yield state
    state["on"] = False
    spans.window()                       # close the session


def _sleep_span(name, dt=0.002):
    with spans.span(name) as sp:
        time.sleep(dt)
    return sp


def test_nesting_and_self_time(collecting):
    with spans.span("root") as root:
        a = _sleep_span("a")
        with spans.span("b") as b:
            _sleep_span("c")
            _sleep_span("c")
        time.sleep(0.002)
    w = spans.window()
    assert w["root"]["n"] == 1 and w["root"]["parent"] is None
    assert w["a"]["parent"] == "root" and w["c"]["parent"] == "b"
    assert w["c"]["n"] == 2
    assert w["root"]["s"] == pytest.approx(root.s)
    assert w["root"]["self_s"] == pytest.approx(root.s - a.s - b.s)
    assert w["b"]["self_s"] == pytest.approx(b.s - w["c"]["s"])
    assert w["root"]["self_s"] >= 0.002 and w["a"]["s"] >= 0.002
    assert a.s == pytest.approx(w["a"]["s"])


def test_counters_are_inclusive_of_enclosing_spans(collecting):
    with spans.span("root"):
        spans.count("x", 1)
        with spans.span("mid"):
            with spans.span("leaf"):
                spans.count("x", 10)
                spans.count("y", 5)
        with spans.span("mid"):
            spans.count("x", 100)
    w = spans.window()
    assert w["root"]["x"] == 111 and w["root"]["y"] == 5
    assert w["mid"]["x"] == 110 and w["leaf"]["x"] == 10
    assert w["mid"]["y"] == 5 and w["leaf"]["y"] == 5


def test_crossings_count_the_bytes_that_move(collecting):
    host = np.zeros((4, 8), np.float32)
    with spans.span("root"):
        dev = spans.to_device(host)
        spans.to_device(dev)                 # already on the device
        back = spans.to_host(dev * 2)
    w = spans.window()
    assert w["root"]["h2d_bytes"] == host.nbytes == 128
    assert w["root"]["d2h_bytes"] == back.nbytes == 128


def test_a_session_starts_afresh(collecting):
    with spans.span("first"):
        spans.count("x", 1)
    with spans.span("first"):
        pass
    assert spans.window()["first"]["n"] == 2
    collecting["on"] = False
    spans.window()                       # read after the profiler stopped
    collecting["on"] = True
    with spans.span("second"):
        pass
    w = spans.window()
    assert set(w) == {"second"}


def test_nothing_is_kept_outside_a_profiler_session(collecting):
    with spans.span("kept"):
        pass
    collecting["on"] = False
    with spans.span("dropped") as sp:
        spans.count("x", 1)
        with spans.span("child"):
            pass
    assert sp.s > 0                      # timed all the same
    assert set(spans.window()) == {"kept"}


def test_a_profiler_session_is_what_gets_kept(tmp_path):
    with spans.span("before"):
        pass
    with jax.profiler.trace(str(tmp_path)):
        with spans.span("inside"):
            spans.count("x", 3)
    with spans.span("after"):
        pass
    w = spans.window()
    assert set(w) == {"inside"} and w["inside"]["x"] == 3


def test_each_thread_keeps_its_own_stack(collecting):
    seen = {}

    def other():
        with spans.span("worker"):
            spans.count("x", 7)
        seen["done"] = True

    with spans.span("main"):
        t = threading.Thread(target=other)
        t.start()
        t.join()
        spans.count("x", 1)
    w = spans.window()
    assert seen["done"]
    assert w["worker"]["parent"] is None and w["worker"]["x"] == 7
    assert w["main"]["x"] == 1


# -- the served path ----------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    params = IB.mem_init(jax.random.PRNGKey(0), CFG, RC)
    return params, multimodal_pairs(0, 64, CFG)


def _engine(params):
    return EmbeddingEngine(params, CFG, RC, modality="vision", policy="fixed",
                           fixed_exit=4, max_batch=16, fw_kw=FW)


def _served(model):
    params, data = model
    eng = _engine(params)
    eng.submit_batch(np.arange(32), data.items["vision"][:32])
    eng.drain()
    q = QueryEngine(params, CFG, RC, store=eng.store,
                    refine_fn=eng.refine_fn(), query_modality="text",
                    fw_kw=FW, search_impl="device")
    return eng, q


def test_per_round_s_is_read_from_the_round_spans(model, collecting):
    eng, q = _served(model)
    texts = model[1].items["text"]
    collecting["on"] = False
    q.query_batch(texts[:4], k=8)        # compile outside the session
    spans.window()
    collecting["on"] = True
    res = q.query_batch(texts[4:8], k=8)
    w = spans.window()
    assert w["query.query_batch"]["n"] == 1
    assert w["store.get_embeddings"]["n"] == 1   # one span for the round
    assert res[0].n_refined > 0
    for name in ("filter", "verify", "refine", "match"):
        assert w[f"query.{name}"]["parent"] == "query.query_batch"
        assert w[f"query.{name}"]["n"] == 1
        assert res[0].per_round_s[name] == pytest.approx(
            w[f"query.{name}"]["s"] / 4, rel=1e-12)
    assert res[0].latency_s == pytest.approx(
        sum(res[0].per_round_s.values()))
    # the single-query path times the same rounds with the same spans
    collecting["on"] = False
    spans.window()
    collecting["on"] = True
    one = q.query(texts[9], k=8)
    w = spans.window()
    assert set(one.per_round_s) == {"filter", "verify", "refine", "match"}
    for name, v in one.per_round_s.items():
        assert v == pytest.approx(w[f"query.{name}"]["s"], rel=1e-12)


def _nbytes(*arrays):
    return sum(int(np.asarray(a).nbytes) for a in arrays)


def test_drain_byte_counters_are_the_crossing_arrays(model, collecting,
                                                     monkeypatch):
    params, data = model
    eng = _engine(params)
    up, down = [], []
    sup = eng._jit_superficial

    def superficial(p, lo, x):
        h, pooled = sup(p, lo, x)
        up.append(x)
        down.extend([h, pooled])
        return h, pooled
    monkeypatch.setattr(eng, "_jit_superficial", superficial)
    cont = eng._continue_fn

    def continue_fn(start, end):
        fn = cont(start, end)

        def run(p, lo, h):
            out = fn(p, lo, h)
            up.append(h)
            down.append(out)
            return out
        return run
    monkeypatch.setattr(eng, "_continue_fn", continue_fn)
    quantize = store_mod._quantize_items

    def quantize_on_device(hs):
        packed, scale = quantize(hs)
        down.extend([packed, scale])
        return packed, scale
    monkeypatch.setattr(store_mod, "_quantize_items", quantize_on_device)
    eng.submit_batch(np.arange(48), data.items["vision"][:48])
    eng.drain()
    w = spans.window()
    d = w["engine.drain"]
    assert d["items"] == 48 and w["engine.superficial"]["n"] == 3
    assert d["h2d_bytes"] == _nbytes(*up)
    assert d["d2h_bytes"] == _nbytes(*down)
    assert (w["engine.superficial"]["h2d_bytes"] +
            w["engine.continue"]["h2d_bytes"] == d["h2d_bytes"])
    # the store quantized the float32 embeddings on the host and the
    # cached states (as the tower left them) on the device: their codes
    # and scales are the store's crossings
    S, dm = CFG.tower("vision").n_tokens + 1, CFG.tower("vision").d_model
    assert w["store.add_batch"]["quantized_bytes"] == 4 * 48 * CFG.embed_dim
    assert w["store.quantize"]["device_quantized_bytes"] == \
        48 * S * dm * np.dtype(CFG.dtype).itemsize
    assert w["store.quantize"]["d2h_bytes"] == 48 * S * (dm // 2 + 4)
    assert "h2d_bytes" not in w["store.quantize"]
    assert eng.stats.wall_s == pytest.approx(d["s"])
    assert eng.stats.group_batches == w["engine.continue"]["n"]


def test_bank_refresh_counters_are_the_banks_own(model, collecting):
    eng, q = _served(model)
    params, data = model
    texts = data.items["text"]
    collecting["on"] = False
    q.query_batch(texts[:4], k=8)        # the bank is built outside
    eng.submit_batch(np.arange(100, 108), data.items["vision"][32:40])
    eng.drain()
    spans.window()
    bank = eng.store._bank
    rows0, bytes0 = bank.h2d_rows, bank.h2d_bytes
    collecting["on"] = True
    q.query_batch(texts[4:8], k=8)
    w = spans.window()
    sync = w["bank.sync_dispatch"]
    assert sync["parent"] == "store.search_batch"
    assert sync["bank_rows"] == bank.h2d_rows - rows0 >= 8
    assert sync["h2d_bytes"] == bank.h2d_bytes - bytes0


def test_refine_byte_counters_are_the_crossing_arrays(model, collecting,
                                                      monkeypatch):
    eng, q = _served(model)
    texts = model[1].items["text"]
    collecting["on"] = False
    q.query_batch(texts[:4], k=8)        # compile outside the session
    spans.window()
    up, down = [], []
    deq = store_mod.dequantize_int4

    def dequantize(packed, scale):
        out = deq(packed, scale)
        up.extend([packed, scale])
        down.append(out)
        return out
    monkeypatch.setattr(store_mod, "dequantize_int4", dequantize)
    cont = eng._continue_fn

    def continue_fn(start, end):
        fn = cont(start, end)

        def run(p, lo, h):
            out = fn(p, lo, h)
            up.append(h)
            down.append(out)
            return out
        return run
    monkeypatch.setattr(eng, "_continue_fn", continue_fn)
    collecting["on"] = True
    res = q.query_batch(texts[4:8], k=8)
    w = spans.window()
    assert sum(r.n_refined for r in res) > 0
    r3 = w["query.refine"]
    assert r3["h2d_bytes"] == _nbytes(*up)
    assert r3["d2h_bytes"] == _nbytes(*down)
    assert w["engine.refine_continue"]["n"] >= 1
    parts = ("store.get_embeddings", "store.cached_activations",
             "engine.refine_continue")
    assert sum(w[p].get("h2d_bytes", 0) for p in parts) == r3["h2d_bytes"]
