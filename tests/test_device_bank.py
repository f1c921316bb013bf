"""DeviceBank + fused int4 scan + numpy quantize parity + refine_round.

The multi-device sharded cases run in subprocesses (the main process must
stay at one CPU device for the rest of the suite), mirroring
tests/test_distributed.py.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import retrieval as RT
from repro.core.quantize import (dequantize_int4, dequantize_int4_np,
                                 quantize_int4, quantize_int4_np)
from repro.core.store import EmbeddingStore
from repro.kernels.retrieval_topk.ops import retrieval_topk_int4
from repro.kernels.retrieval_topk.ref import (retrieval_topk_int4_reference,
                                              retrieval_topk_reference)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _embs(n, e=32, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, e)).astype(np.float32)
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


def run_py(code: str, n_dev: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


# ---------------------------------------------------------------------------
# numpy quantize parity (store inserts now run host-side)
# ---------------------------------------------------------------------------


def _near_half_steps(m, n, rng, dtype):
    """(rows, n) values of ``dtype`` at (k + 0.5) * scale and one ulp of
    ``dtype`` either side, scale = max / 7 of each row's max ``m``."""
    m = np.asarray(m, np.float32).reshape(-1, 1)
    scale = np.maximum(m / np.float32(7), np.float32(1e-12))
    k = rng.integers(-8, 7, (len(m), n)).astype(np.float32)
    v = ((k + np.float32(0.5)) * scale).astype(dtype)
    inf = np.asarray(np.inf, dtype)
    side = rng.integers(-1, 2, v.shape)
    v = np.where(side > 0, np.nextafter(v, inf) if dtype == np.float32
                 else (v.astype(np.float32) * (1 + 2.0 ** -8)).astype(dtype),
                 v)
    v = np.where(side < 0, np.nextafter(v, -inf) if dtype == np.float32
                 else (v.astype(np.float32) * (1 - 2.0 ** -8)).astype(dtype),
                 v)
    return np.clip(v.astype(np.float32), -m, m).astype(dtype)


def _parity_input(shape):
    rng = np.random.default_rng(0)
    if shape == (4096, 32):         # float32 rows of near-half steps
        m = rng.uniform(0.5, 2, shape[0]) * 10.0 ** rng.integers(-8, 8,
                                                                 shape[0])
        x = _near_half_steps(m, shape[1], rng, np.float32)
        x[:, 0] = m
        return x
    if shape[-2:] != (257, 1280):   # small float32 rows of mixed magnitude
        x = (rng.standard_normal(shape) *
             rng.choice([1e-6, 1.0, 100.0], shape)).astype(np.float32)
        x[..., 0] = 0.0  # exercise the zero / tiny-scale guard
        return x
    # superficial states as the tower leaves them: bf16, one row all zero,
    # rows whose max is 7 * 2**e (exact half-step ties) or seeded, holding
    # values a bf16 ulp either side of (k + 0.5) * scale
    x = (rng.standard_normal(shape) *
         rng.choice([1e-3, 1.0, 30.0], shape[:-1] + (1,))).astype(jnp.bfloat16)
    x[0, 0] = 0
    rows = x[:, 1:65].reshape(-1, shape[-1])
    m = np.where(rng.random(len(rows)) < 0.5,
                 7 * 2.0 ** rng.integers(-10, 6, len(rows)),
                 np.abs(rows.astype(np.float32)).max(-1)).astype(np.float32)
    rows[:, :256] = _near_half_steps(m, 256, rng, jnp.bfloat16)
    rows[:, 256] = m
    x[:, 1:65] = rows.reshape(shape[0], 64, shape[-1])
    return x


@pytest.mark.parametrize("shape", [(1, 8), (64, 32), (5, 7, 16), (4096, 32),
                                   (8, 257, 1280), (64, 257, 1280)])
def test_quantize_int4_np_bit_exact_parity(shape):
    """The device rule is the numpy rule, bit for bit: small float32 rows,
    float32 values an ulp from a half step, and the ingest cell's bf16
    superficial states (a full 64-item chunk of them on a TPU only)."""
    if shape[0] == 64 and len(shape) == 3 and jax.default_backend() != "tpu":
        pytest.skip("a 64-item chunk of cell-width states: TPU only")
    x = _parity_input(shape)
    # compiled, as every caller on the served path runs it (XLA may fold
    # the division by 7 into a product there)
    pj, sj = jax.jit(quantize_int4)(jnp.asarray(x))
    pn, sn = quantize_int4_np(x)
    np.testing.assert_array_equal(np.asarray(pj), pn)
    np.testing.assert_array_equal(np.asarray(sj), sn)
    np.testing.assert_array_equal(np.asarray(dequantize_int4(pj, sj)),
                                  dequantize_int4_np(pn, sn))


def test_quantize_int4_np_half_even_rounding():
    """jnp.round and np.rint both round half to even — the parity hinges on
    it, so pin the exact half-way cases."""
    h = np.array([[0.5, -0.5, 1.5, -1.5, 2.5, -2.5, 3.5, -3.5]],
                 np.float32) * 7
    pj, _ = quantize_int4(jnp.asarray(h))
    pn, _ = quantize_int4_np(h)
    np.testing.assert_array_equal(np.asarray(pj), pn)


def test_store_quantizes_device_states_as_host_states(monkeypatch):
    """Cached states handed over on the device and on the host are stored
    alike; only the device input counts ``device_quantized_bytes``."""
    from repro.core import spans
    on = [False]
    monkeypatch.setattr(spans, "_collecting", lambda: on[0])
    h = _parity_input((8, 257, 1280))[:4, :, :64]
    embs = _embs(4, 16)
    stores, windows = [], []
    # one device array per item (as a drain hands them over), then the host
    # array; one profiler session each
    for cached in ([jnp.asarray(r) for r in h], h):
        st = EmbeddingStore(16, capacity=4)
        on[0] = True
        with spans.span("root"):
            st.add_batch(np.arange(4), embs, [0] * 4, [2] * 4,
                         cached_hs=cached)
        on[0] = False
        stores.append(st)
        windows.append(spans.window())
    (dev, host), (wd, wh) = stores, windows
    for u in range(4):
        for a, b in zip(dev._act_cache[u][:2], host._act_cache[u][:2]):
            assert type(a) is np.ndarray and a.dtype == b.dtype
            np.testing.assert_array_equal(a, b)
        assert dev._act_cache[u][2:] == host._act_cache[u][2:] == \
            ((257, 64), 2)
    ad, ah = dev.cached_activations(range(4)), host.cached_activations(range(4))
    for u in range(4):
        np.testing.assert_array_equal(ad[u][0], ah[u][0])
        np.testing.assert_array_equal(ad[u][0], dequantize_int4_np(
            *quantize_int4_np(h[u])))
    np.testing.assert_array_equal(dev.get_embeddings(np.arange(4)),
                                  host.get_embeddings(np.arange(4)))
    assert wd["store.quantize"]["device_quantized_bytes"] == h.nbytes
    assert wd["store.quantize"]["quantized_bytes"] == embs.nbytes
    # only the codes and scales come down
    assert wd["store.quantize"]["d2h_bytes"] == 4 * 257 * (32 + 4)
    assert "device_quantized_bytes" not in wh["store.quantize"]
    assert "d2h_bytes" not in wh["store.quantize"]
    assert wh["store.quantize"]["quantized_bytes"] == \
        4 * h.size + embs.nbytes


def test_store_add_runs_without_device_dispatch():
    """Per-item add must not touch jax at all (host-side quantize)."""
    import unittest.mock as mock
    st = EmbeddingStore(16, capacity=4)
    with mock.patch.object(jnp, "asarray",
                           side_effect=AssertionError("device dispatch")):
        st.add(1, _embs(1, 16)[0], exit_idx=0, exit_layer=1)
        st.add_batch([2, 3], _embs(2, 16, seed=1), [0, 0], [1, 1],
                     cached_hs=np.zeros((2, 3, 16), np.float32))
    assert len(st) == 3


# ---------------------------------------------------------------------------
# fused packed-int4 scan: all impls vs the dequant-all oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("N,n_valid,block_n", [(77, None, 32), (130, 97, 32),
                                               (1000, 800, 128)])
def test_int4_topk_impls_match_oracle(N, n_valid, block_n):
    rng = np.random.default_rng(0)
    E, Q, k = 32, 5, 7
    x = rng.standard_normal((N, E)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((Q, E)).astype(np.float32))
    p, s = quantize_int4(jnp.asarray(x))
    sr, ir = retrieval_topk_int4_reference(q, p, s, k, n_valid=n_valid)
    for impl, kw in (("xla", dict(block_n=block_n)),
                     ("pallas", dict(block_q=4, block_n=block_n,
                                     interpret=True)),
                     ("ref", {})):
        sa, ia = retrieval_topk_int4(q, p, s, k, impl=impl, n_valid=n_valid,
                                     **kw)
        np.testing.assert_allclose(np.asarray(sa), np.asarray(sr), atol=1e-4)
        for r in range(Q):
            assert (set(np.asarray(ia[r]).tolist())
                    == set(np.asarray(ir[r]).tolist())), impl
        if n_valid is not None:
            assert int(np.asarray(ia).max()) < n_valid


def test_int4_topk_matches_fp32_dense_scan_to_quant_error():
    """The fused dequant scan over the int4 slab == the dense scan over the
    dequantized slab (same rows, scores exactly equal up to matmul order)."""
    rng = np.random.default_rng(1)
    x = _embs(300, 64, seed=2)
    q = jnp.asarray(rng.standard_normal((4, 64)).astype(np.float32))
    p, s = quantize_int4(jnp.asarray(x))
    dense = dequantize_int4(p, s)
    sd, idd = retrieval_topk_reference(q, dense, 9, normalize=False)
    si, ii = retrieval_topk_int4(q, p, s, 9, impl="xla", normalize=False)
    np.testing.assert_allclose(np.asarray(si), np.asarray(sd), atol=1e-5)
    for r in range(4):
        assert (set(np.asarray(ii[r]).tolist())
                == set(np.asarray(idd[r]).tolist()))


def test_int4_topk_rejects_unknown_impl():
    p, s = quantize_int4(jnp.asarray(_embs(8, 16)))
    with pytest.raises(ValueError):
        retrieval_topk_int4(jnp.zeros((1, 16)), p, s, 2, impl="cuda")


# ---------------------------------------------------------------------------
# store device path: parity + incremental refresh invariants
# ---------------------------------------------------------------------------


def test_device_search_matches_numpy_path():
    E = 32
    st = EmbeddingStore(E, capacity=8)
    embs = _embs(200, E)
    st.add_batch(np.arange(200), embs, np.zeros(200), np.ones(200))
    q = _embs(6, E, seed=3)
    nu, ns = st.search_batch(q, 10, impl="numpy")
    du, ds = st.search_batch(q, 10, impl="device")  # auto-attaches the bank
    assert st.device_bank is not None
    np.testing.assert_allclose(ds, ns, atol=1e-4)
    for a, b in zip(nu, du):
        assert set(a.tolist()) == set(b.tolist())


def test_device_refresh_parity_interleaved_mutations():
    """Dirty-row refresh parity after interleaved add_batch/upgrade_batch,
    across a device-side slab doubling — and only dirty rows travel."""
    E = 16
    st = EmbeddingStore(E, capacity=8)
    embs = _embs(400, E)
    st.add_batch(np.arange(100), embs[:100], np.zeros(100), np.ones(100))
    q = _embs(5, E, seed=4)
    st.search_batch(q, 8, impl="device")            # warm-up sync
    bank = st.device_bank
    b0 = bank.h2d_bytes
    # steady state: repeated queries move zero bytes (exact invariant)
    for _ in range(3):
        st.search_batch(q, 8, impl="device")
    assert bank.h2d_bytes == b0

    # interleave: upgrade a few rows, then grow the slab past capacity
    st.upgrade_batch([3, 57], _embs(2, E, seed=9))
    st.add_batch(np.arange(100, 400), embs[100:], np.zeros(300),
                 np.ones(300))                       # forces host+device grow
    st.upgrade_batch([250], _embs(1, E, seed=10))
    du, _ = st.search_batch(q, 8, impl="device")
    nu, _ = st.search_batch(q, 8, impl="numpy")
    for a, b in zip(nu, du):
        assert set(a.tolist()) == set(b.tolist())
    assert bank.n_grows >= 1                         # doubled on device
    # refresh moved exactly the dirty rows (not the whole slab): 2 upgrades
    # + 300 adds + 1 upgrade of an already-dirty row = 302 unique rows (the
    # bitmap dedups overlapping dirt)
    moved = bank.h2d_rows - 100
    assert moved == 302
    # and far less traffic than one call of the re-upload path (full fp32
    # slab; at this toy E the scatter *indices* dominate the int4 payload,
    # so compare against what the old path would actually have moved)
    assert bank.h2d_bytes - b0 < st._dense.nbytes


def test_device_search_after_upgrade_sees_new_rows():
    E = 16
    st = EmbeddingStore(E, capacity=4)
    st.add_batch(np.arange(10), _embs(10, E), np.zeros(10), np.ones(10))
    st.search_batch(_embs(1, E, seed=5), 1, impl="device")
    target = _embs(1, E, seed=42)[0]
    st.upgrade(7, target)
    u, _ = st.search_batch(target[None], 1, impl="device")
    assert u[0, 0] == 7


def test_device_path_fp32_store_mode():
    """store_int4=False banks fp32 rows and searches them with the dense
    kernel — same parity contract."""
    E = 16
    st = EmbeddingStore(E, store_int4=False, capacity=4)
    st.add_batch(np.arange(50), _embs(50, E), np.zeros(50), np.ones(50))
    q = _embs(4, E, seed=6)
    nu, ns = st.search_batch(q, 5, impl="numpy")
    du, ds = st.search_batch(q, 5, impl="device")
    np.testing.assert_allclose(ds, ns, atol=1e-5)
    for a, b in zip(nu, du):
        assert set(a.tolist()) == set(b.tolist())


def test_reupload_paths_count_transfer_bytes():
    E = 16
    st = EmbeddingStore(E, capacity=8)
    st.add_batch(np.arange(30), _embs(30, E), np.zeros(30), np.ones(30))
    q = _embs(2, E, seed=7)
    st.search_batch(q, 4, impl="xla")
    assert st.upload_calls == 1
    assert st.upload_bytes == st._dense.nbytes  # full fp32 capacity slab
    st.search_batch(q, 4, impl="numpy")         # host path: no upload
    assert st.upload_calls == 1


# ---------------------------------------------------------------------------
# sharded search (subprocess: single-host multi-device CPU override)
# ---------------------------------------------------------------------------


@pytest.mark.tier2  # 8-device subprocess: slow; `make tier2` runs it
def test_sharded_search_matches_single_device():
    run_py("""
        import numpy as np, jax
        from repro.core.store import EmbeddingStore
        assert len(jax.devices()) == 8
        rng = np.random.default_rng(0)
        E = 64
        embs = rng.standard_normal((500, E)).astype(np.float32)
        q = rng.standard_normal((6, E)).astype(np.float32)

        st = EmbeddingStore(E, capacity=8)
        st.add_batch(np.arange(300), embs[:300], np.zeros(300), np.ones(300))
        st.attach_device_bank(jax.devices())        # sharded, 8 ways
        assert st.device_bank.n_shards == 8

        single = EmbeddingStore(E, capacity=8)
        single.add_batch(np.arange(300), embs[:300], np.zeros(300),
                         np.ones(300))
        single.attach_device_bank(jax.devices()[:1])

        for k in (3, 10, 50):                        # incl. k > rows/shard
            su, ss = st.search_batch(q, k, impl="device")
            du, ds = single.search_batch(q, k, impl="device")
            np.testing.assert_allclose(ss, ds, atol=1e-4)
            for a, b in zip(su, du):
                assert set(a.tolist()) == set(b.tolist())

        # mutations + growth keep the shards in sync
        for s2 in (st, single):
            s2.upgrade_batch([5, 17], embs[400:402])
            s2.add_batch(np.arange(300, 500), embs[300:], np.zeros(200),
                         np.ones(200))
        su, ss = st.search_batch(q, 10, impl="device")
        du, ds = single.search_batch(q, 10, impl="device")
        nu, _ = single.search_batch(q, 10, impl="numpy")
        for a, b, c in zip(su, du, nu):
            assert set(a.tolist()) == set(b.tolist()) == set(c.tolist())
        # steady state still moves zero bytes when sharded
        b0 = st.device_bank.h2d_bytes
        st.search_batch(q, 10, impl="device")
        assert st.device_bank.h2d_bytes == b0
        print("OK sharded")
    """)


# ---------------------------------------------------------------------------
# refine_round consolidation
# ---------------------------------------------------------------------------


def _mk_store(n=12, E=16):
    st = EmbeddingStore(E, capacity=8)
    embs = _embs(n, E)
    st.add_batch(np.arange(n), embs, np.zeros(n), np.ones(n))
    return st, embs


def test_refine_round_successes_retries_past_failures():
    """budget_mode='successes' == the seed's sequential loop: candidates
    past a failed one are still attempted until `budget` succeed."""
    st, embs = _mk_store()
    attempted = []

    def flaky(uids):
        uids = np.asarray(uids).ravel()
        attempted.extend(uids.tolist())
        return {int(u): embs[int(u)] for u in uids if u % 2 == 0}

    cand = np.arange(8, dtype=np.int64)
    fine, n_ref = RT.refine_round(st, [cand], flaky, 3,
                                  budget_mode="successes")
    assert n_ref == [3]
    # rounds: [0,1,2] -> 0,2 ok; [3,4] -> 4 ok; budget met
    assert attempted == [0, 1, 2, 3, 4]
    assert st.n_fine == 3
    np.testing.assert_allclose(fine[0][0], embs[0], atol=1e-5)


def test_refine_round_attempts_caps_without_retry():
    st, embs = _mk_store()
    attempted = []

    def flaky(uids):
        uids = np.asarray(uids).ravel()
        attempted.extend(uids.tolist())
        return {int(u): embs[int(u)] for u in uids if u % 2 == 0}

    fine, n_ref = RT.refine_round(st, [np.arange(8, dtype=np.int64)], flaky,
                                  3, budget_mode="attempts")
    assert attempted == [0, 1, 2]       # one round, capped, no retry
    assert n_ref == [2]                 # only the even ones succeeded


def test_refine_round_dedups_shared_candidates_across_queries():
    st, embs = _mk_store()
    calls = []

    def refine(uids):
        uids = np.asarray(uids).ravel()
        calls.append(uids.tolist())
        return {int(u): embs[int(u)] for u in uids}

    qs = [np.array([1, 2, 3], np.int64), np.array([2, 3, 4], np.int64)]
    fine, n_ref = RT.refine_round(st, qs, refine, None,
                                  budget_mode="attempts")
    assert len(calls) == 1 and calls[0] == [1, 2, 3, 4]  # shared uids once
    assert n_ref == [3, 3]              # ...but counted per requesting query
    np.testing.assert_allclose(fine[1][0], embs[2], atol=1e-5)
    assert st.n_fine == 4


def test_refine_round_no_fn_returns_fallbacks():
    st, _ = _mk_store()
    fine, n_ref = RT.refine_round(st, [np.array([1, 2], np.int64)], None, 5)
    assert n_ref == [0] and fine[0].shape == (2, 16)
    assert st.n_fine == 0


@pytest.mark.parametrize("mode", ["successes", "attempts"])
def test_refine_round_empty_uid_batch(mode):
    """An empty candidate list never invokes refine_fn and returns an empty
    (0, E) fallback matrix — for a lone empty query and mixed with a
    populated one."""
    st, embs = _mk_store()
    calls = []

    def refine(uids):
        calls.append(np.asarray(uids).tolist())
        return {int(u): embs[int(u)] for u in np.asarray(uids).ravel()}

    empty = np.zeros((0,), np.int64)
    fine, n_ref = RT.refine_round(st, [empty], refine, 4, budget_mode=mode)
    assert n_ref == [0] and fine[0].shape == (0, 16)
    assert calls == []                      # all-empty short-circuits
    fine, n_ref = RT.refine_round(st, [empty, np.array([2, 3], np.int64)],
                                  refine, 4, budget_mode=mode)
    assert n_ref == [0, 2] and fine[0].shape == (0, 16)
    assert sum(calls, []) == [2, 3]


@pytest.mark.parametrize("mode", ["successes", "attempts"])
def test_refine_round_all_misses_terminates(mode):
    """A refine_fn that never succeeds must terminate (the 'successes' retry
    loop exhausts the pending list rather than spinning), refine nothing,
    and keep the coarse fallbacks."""
    st, _ = _mk_store()
    attempted = []

    def never(uids):
        attempted.extend(np.asarray(uids).ravel().tolist())
        return {}

    cand = np.arange(6, dtype=np.int64)
    fine, n_ref = RT.refine_round(st, [cand], never, 2, budget_mode=mode)
    assert n_ref == [0]
    assert st.n_fine == 0
    assert fine[0].shape == (6, 16)         # fallbacks intact
    if mode == "attempts":
        assert attempted == [0, 1]          # capped, single round
    else:
        assert attempted == list(range(6))  # retried to exhaustion, once each


@pytest.mark.parametrize("mode", ["successes", "attempts"])
def test_refine_round_budget_zero_attempts_nothing(mode):
    st, embs = _mk_store()
    calls = []

    def refine(uids):
        calls.append(np.asarray(uids).tolist())
        return {int(u): embs[int(u)] for u in np.asarray(uids).ravel()}

    fine, n_ref = RT.refine_round(st, [np.arange(5, dtype=np.int64)], refine,
                                  0, budget_mode=mode)
    assert calls == [] and n_ref == [0]
    assert fine[0].shape == (5, 16) and st.n_fine == 0


def test_refine_round_budget_zero_via_speculative_retrieve():
    """End-to-end: refine_budget=0 serves pure coarse results (no refine
    call, no upgrades) through the full pipeline."""
    st, embs = _mk_store()

    def boom(uids):  # must never be called
        raise AssertionError("refine_fn called despite budget=0")

    res = RT.speculative_retrieve(st, [embs[4]], fine_query=embs[4], k=6,
                                  refine_fn=boom, refine_budget=0)
    assert res.uids[0] == 4 and res.n_refined == 0
    assert st.n_fine == 0
