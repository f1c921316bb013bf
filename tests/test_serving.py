"""End-to-end serving integration: engine policies, store invariants,
query-time refinement, upgrade-on-query, healing + P-LoRA pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MEMConfig, RecallConfig, TowerConfig
from repro.core import exits as EX
from repro.core import preexit as PE
from repro.core.healing import HealConfig, heal_tower
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


@pytest.fixture(scope="module")
def service():
    key = jax.random.PRNGKey(0)
    params = IB.mem_init(key, CFG, RC)
    data = multimodal_pairs(0, 96, CFG)
    vis = jnp.asarray(data.items["vision"])
    out = IB.mem_embed_all_exits(params, CFG, RC, "vision", vis, **FW)
    labels = EX.optimal_exit_labels(out["exit_embs"], out["exit_embs"][-1])
    sup = IB.tower_forward(params, CFG, RC, "vision", vis,
                           layer_end=RC.superficial_layers, **FW)["pooled"][-1]
    predictor, _ = PE.train_predictor(key, sup, labels,
                                      n_exits=len(out["exits"]), hidden=32,
                                      steps=80)
    return params, predictor, data


def _engine(params, predictor, policy="recall"):
    return EmbeddingEngine(params, CFG, RC, modality="vision",
                           predictor_params=predictor, policy=policy,
                           max_batch=16, fw_kw=FW)


def test_engine_embeds_and_stores(service):
    params, predictor, data = service
    eng = _engine(params, predictor)
    eng.submit_batch(np.arange(32), data.items["vision"][:32])
    stats = eng.drain()
    assert stats.n_embedded == 32 and len(eng.store) == 32
    assert stats.avg_layers <= CFG.tower("vision").n_layers


def test_full_policy_matches_direct_fine_embedding(service):
    params, predictor, data = service
    eng = _engine(params, predictor, policy="full")
    eng.submit_batch(np.arange(16), data.items["vision"][:16])
    eng.drain()
    direct = np.asarray(IB.mem_embed(params, CFG, RC, "vision",
                                     jnp.asarray(data.items["vision"][:16]),
                                     **FW))
    stored = eng.store.dense_matrix()
    # int4 storage quantization is the only difference
    assert np.abs(stored - direct).max() < 1.0 / 7 + 1e-3


def test_refine_fn_reproduces_full_embedding(service):
    """Cached-activation refinement == direct full embedding up to the INT4
    cache quantization error."""
    params, predictor, data = service
    eng = _engine(params, predictor, policy="fixed")
    eng.fixed_exit = RC.superficial_layers + 1
    eng.submit_batch(np.arange(8), data.items["vision"][:8])
    eng.drain()
    refine = eng.refine_fn()
    direct = np.asarray(IB.mem_embed(params, CFG, RC, "vision",
                                     jnp.asarray(data.items["vision"][:1]),
                                     **FW))[0]
    got = refine(0)
    cos = float(np.dot(got, direct))
    # INT4 activation-cache quantization error propagates through the
    # remaining layers (paper §3.4 accepts this); exactness without
    # quantization is covered by test_refine_from_cached_is_exact.
    assert cos > 0.85, cos


@pytest.mark.parametrize("past_prefix", [1, 0])
def test_drain_caches_states_quantized_on_the_device(service, monkeypatch,
                                                     past_prefix):
    """A drain stores each item's cached state as the int4 rule of the
    superficial state's host copy, quantized on the device: the host rule
    never sees the states, only the embeddings. Items exiting past the
    superficial prefix (the continuation's upload) and within it."""
    from repro.core import store as store_mod
    params, predictor, data = service
    eng = _engine(params, predictor, policy="fixed")
    eng.fixed_exit = RC.superficial_layers + past_prefix
    assert eng.fixed_exit in eng.exits
    sup, host_rule_shapes = [], []
    superficial = eng._jit_superficial

    def keep(p, lo, x):
        h, pooled = superficial(p, lo, x)
        sup.append(np.asarray(h))
        return h, pooled
    host_rule = store_mod.quantize_int4_np

    def watch(x):
        host_rule_shapes.append(np.shape(x))
        return host_rule(x)
    monkeypatch.setattr(eng, "_jit_superficial", keep)
    monkeypatch.setattr(store_mod, "quantize_int4_np", watch)
    eng.submit_batch(np.arange(40), data.items["vision"][:40])
    eng.drain()
    h = np.concatenate(sup)
    assert len(sup) == 3 and h.shape[0] == 40
    assert host_rule_shapes and all(len(s) == 2 and s[1] == CFG.embed_dim
                                    for s in host_rule_shapes)
    packed, scale = host_rule(h)
    for u in range(40):
        p, sc, shape, layer = eng.store._act_cache[u]
        assert shape == h.shape[1:] and layer == eng.fixed_exit
        np.testing.assert_array_equal(p, packed[u])
        np.testing.assert_array_equal(sc, scale[u])


def test_query_upgrade_on_query(service):
    params, predictor, data = service
    eng = _engine(params, predictor)
    eng.submit_batch(np.arange(32), data.items["vision"][:32])
    eng.drain()
    q = QueryEngine(params, CFG, RC, store=eng.store,
                    refine_fn=eng.refine_fn(), query_modality="text", fw_kw=FW)
    res1 = q.query(data.items["text"][3], k=8)
    assert res1.n_refined > 0
    # §5.3: queried items are permanently upgraded -> second query refines
    # strictly fewer items
    res2 = q.query(data.items["text"][3], k=8)
    assert res2.n_refined < res1.n_refined or res2.n_refined == 0


def test_query_latency_budget(service):
    params, predictor, data = service
    eng = _engine(params, predictor)
    eng.submit_batch(np.arange(24), data.items["vision"][:24])
    eng.drain()
    q = QueryEngine(params, CFG, RC, store=eng.store,
                    refine_fn=eng.refine_fn(), query_modality="text", fw_kw=FW)
    res = q.query(data.items["text"][0], k=10, refine_budget=3)
    assert res.n_refined <= 3


def test_query_batch_matches_sequential_queries(service):
    """Each query_batch result == query() alone against a fresh store:
    identical top-k uids, scores within 1e-5 (the acceptance parity check).
    (Fresh store per sequential query because a batch shares refinements the
    way independent fresh-store queries do, while a mutating sequential loop
    lets earlier upgrades requantize later queries' candidates.)"""
    params, predictor, data = service
    nq = 6

    def build():
        eng = _engine(params, predictor)
        eng.submit_batch(np.arange(32), data.items["vision"][:32])
        eng.drain()
        return QueryEngine(params, CFG, RC, store=eng.store,
                           refine_fn=eng.refine_fn(), query_modality="text",
                           fw_kw=FW)
    seq = [build().query(data.items["text"][i], k=8) for i in range(nq)]
    bat = build().query_batch(data.items["text"][:nq], k=8)
    for i, (a, b) in enumerate(zip(seq, bat)):
        np.testing.assert_array_equal(a.uids, b.uids, err_msg=f"query {i}")
        np.testing.assert_allclose(a.scores, b.scores, atol=1e-5)
        assert a.n_refined == b.n_refined


def test_query_batch_smoke_refines_and_upgrades(service):
    params, predictor, data = service
    eng = _engine(params, predictor)
    eng.submit_batch(np.arange(32), data.items["vision"][:32])
    eng.drain()
    q = QueryEngine(params, CFG, RC, store=eng.store,
                    refine_fn=eng.refine_fn(), query_modality="text", fw_kw=FW)
    res = q.query_batch(data.items["text"][:4], k=8, refine_budget=3)
    assert len(res) == 4
    assert all(r.n_refined <= 3 for r in res)
    assert sum(r.n_refined for r in res) > 0
    assert eng.store.n_fine > 0
    # §5.3: a second identical batch hits upgraded embeddings
    res2 = q.query_batch(data.items["text"][:4], k=8, refine_budget=3)
    assert sum(r.n_refined for r in res2) <= sum(r.n_refined for r in res)
    # non-speculative batch path
    res3 = q.query_batch(data.items["text"][:4], k=8, speculative=False)
    assert all(r.n_refined == 0 and len(r.uids) == 8 for r in res3)


def test_query_engine_with_ivf_index_matches_exhaustive(service):
    """QueryEngine(index='ivf', search_impl='ivf') at full probe fan-out
    serves the same drain results as the exhaustive engine over the same
    corpus (the pruned path covers every assigned row when nprobe ==
    n_clusters) and never falls back. search_impl is explicit because on
    CPU 'auto' deliberately stays on the numpy path."""
    params, predictor, data = service

    def build(**kw):
        eng = _engine(params, predictor)
        eng.submit_batch(np.arange(32), data.items["vision"][:32])
        eng.drain()
        return eng, QueryEngine(params, CFG, RC, store=eng.store,
                                refine_fn=eng.refine_fn(),
                                query_modality="text", fw_kw=FW, **kw)
    _, q_ex = build()
    eng_ivf, q_ivf = build(index="ivf", index_clusters=4, index_min_rows=1,
                           nprobe=4, search_impl="ivf")
    assert eng_ivf.store.ivf_index is not None
    a = q_ex.query_batch(data.items["text"][:4], k=8)
    b = q_ivf.query_batch(data.items["text"][:4], k=8)
    for ra, rb in zip(a, b):
        assert set(ra.uids.tolist()) == set(rb.uids.tolist())
        np.testing.assert_allclose(np.sort(ra.scores), np.sort(rb.scores),
                                   atol=1e-4)
    assert eng_ivf.store.ivf_fallbacks == 0
    eng_ivf.store.ivf_index.check_consistency(
        len(eng_ivf.store),
        eng_ivf.store.rows_of(eng_ivf.store.uids()))


def test_branchynet_policy_runs(service):
    params, predictor, data = service
    eng = _engine(params, predictor, policy="branchynet")
    eng.submit_batch(np.arange(4), data.items["vision"][:4])
    stats = eng.drain()
    assert stats.n_embedded == 4


@pytest.mark.tier2
def test_healing_improves_coarse_alignment():
    """P-LoRA healing must increase cos(coarse, fine) on the healed tower."""
    key = jax.random.PRNGKey(1)
    params = IB.mem_init(key, CFG, RC)
    data = multimodal_pairs(1, 64, CFG)
    vis = jnp.asarray(data.items["vision"])

    fine0 = IB.mem_embed(params, CFG, RC, "vision", vis, **FW)

    def mean_alignment(lora):
        out = IB.mem_embed_all_exits(params, CFG, RC, "vision", vis,
                                     lora=lora, **FW)
        return float(jnp.mean(jnp.sum(out["exit_embs"][0] * fine0, -1)))

    before = mean_alignment(None)
    lora, log = heal_tower(key, params, CFG, RC, "vision", vis,
                           heal_cfg=HealConfig(lr=3e-3, steps_per_phase=25,
                                               batch=32), fw_kw=FW)
    after = mean_alignment(lora)
    assert after > before + 0.02, (before, after)
    assert all(p["loss_last"] <= p["loss_first"] + 0.05 for p in log)
