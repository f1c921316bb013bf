"""Kernel sweeps: every Pallas kernel vs its pure-jnp oracle, plus
hypothesis property tests on the quantizers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.quantize import (dequantize_int4, dequantize_int8,
                                 quantize_int4, quantize_int8)
from repro.kernels.decode_attention.kernel import decode_fwd_pallas
from repro.kernels.decode_attention.ref import decode_attention_reference
from repro.kernels.int4_cache.kernel import (dequantize_int4_pallas,
                                             quantize_int4_pallas)
from repro.kernels.moe_gemm.ops import moe_gemm, sort_by_expert
from repro.kernels.moe_gemm.ref import moe_gemm_reference
from repro.kernels.retrieval_topk.kernel import retrieval_topk_pallas
from repro.kernels.retrieval_topk.ops import resolve_impl
from repro.kernels.retrieval_topk.ref import retrieval_topk_reference
from repro.kernels.rmsnorm.kernel import rmsnorm_pallas
from repro.models.layers import rmsnorm

# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,H,KV,D,window,bkv", [
    (2, 256, 8, 2, 32, 0, 64),
    (3, 100, 4, 4, 16, 0, 32),
    (2, 512, 8, 1, 64, 128, 128),
    (1, 64, 16, 8, 128, 0, 64),
])
def test_decode_pallas_vs_ref(B, S, H, KV, D, window, bkv):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, H, D))
    k = jax.random.normal(ks[1], (B, S, KV, D))
    v = jax.random.normal(ks[2], (B, S, KV, D))
    lengths = jax.random.randint(ks[3], (B,), 1, S + 1)
    ref = decode_attention_reference(q, k, v, lengths, window=window)
    out = decode_fwd_pallas(q, k, v, lengths, window=window, block_kv=bkv)
    np.testing.assert_allclose(out, ref, atol=3e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 3e-2)])
def test_decode_pallas_bf16(dtype, tol):
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (2, 4, 32), dtype)
    k = jax.random.normal(ks[1], (2, 128, 2, 32), dtype)
    v = jax.random.normal(ks[2], (2, 128, 2, 32), dtype)
    lengths = jnp.array([60, 128], jnp.int32)
    ref = decode_attention_reference(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        lengths)
    out = decode_fwd_pallas(q, k, v, lengths, block_kv=64)
    np.testing.assert_allclose(out.astype(jnp.float32), ref, atol=tol)


# ---------------------------------------------------------------------------
# int4 cache
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("N,D,block", [(100, 64, 32), (7, 128, 8), (256, 32, 256)])
def test_int4_pallas_vs_ref(N, D, block):
    x = jax.random.normal(jax.random.PRNGKey(0), (N, D)) * 3
    p_ref, s_ref = quantize_int4(x)
    p_pl, s_pl = quantize_int4_pallas(x, block_rows=block)
    assert bool(jnp.all(p_ref == p_pl))
    np.testing.assert_allclose(s_ref, s_pl, rtol=1e-6)
    x_ref = dequantize_int4(p_ref, s_ref)
    x_pl = dequantize_int4_pallas(p_pl, s_pl, block_rows=block)
    np.testing.assert_allclose(x_ref, x_pl, atol=1e-6)


@pytest.mark.tier2
@settings(deadline=None, max_examples=25)
@given(st.integers(1, 40), st.integers(1, 32), st.floats(0.01, 100.0))
def test_int4_roundtrip_error_bound(n, d2, scale):
    """Property: per-row abs error <= scale_row/2 (half an int4 step)."""
    d = 2 * d2
    x = jnp.asarray(np.random.default_rng(n * d).standard_normal((n, d)) * scale,
                    jnp.float32)
    p, s = quantize_int4(x)
    xr = dequantize_int4(p, s)
    err = jnp.abs(x - xr)
    assert bool(jnp.all(err <= s * 0.5 + 1e-6))


@pytest.mark.tier2
@settings(deadline=None, max_examples=25)
@given(st.integers(1, 40), st.integers(1, 64))
def test_int8_roundtrip_error_bound(n, d):
    x = jnp.asarray(np.random.default_rng(n + d).standard_normal((n, d)), jnp.float32)
    q, s = quantize_int8(x)
    xr = dequantize_int8(q, s)
    assert bool(jnp.all(jnp.abs(x - xr) <= s * 0.5 + 1e-6))


def test_int4_idempotent():
    """Quantizing already-quantized values is exact."""
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 32))
    p, s = quantize_int4(x)
    xr = dequantize_int4(p, s)
    p2, s2 = quantize_int4(xr)
    np.testing.assert_allclose(dequantize_int4(p2, s2), xr, atol=1e-6)


# ---------------------------------------------------------------------------
# retrieval top-k
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("Q,N,E,k,bq,bn", [
    (10, 1000, 32, 8, 4, 128),
    (3, 77, 16, 5, 8, 32),
    (16, 4096, 64, 16, 16, 512),
])
def test_topk_pallas_vs_ref(Q, N, E, k, bq, bn):
    q = jax.random.normal(jax.random.PRNGKey(1), (Q, E))
    bank = jax.random.normal(jax.random.PRNGKey(2), (N, E))
    sr, ir = retrieval_topk_reference(q, bank, k)
    sp, ip = retrieval_topk_pallas(q, bank, k, block_q=bq, block_n=bn,
                                   interpret=True)
    np.testing.assert_allclose(sr, sp, atol=1e-5)
    # ids compared as sets per row (ties may permute)
    for r in range(Q):
        assert set(np.asarray(ir[r]).tolist()) == set(np.asarray(ip[r]).tolist())


def test_topk_unnormalized():
    q = jax.random.normal(jax.random.PRNGKey(3), (4, 8))
    bank = jax.random.normal(jax.random.PRNGKey(4), (64, 8))
    sr, ir = retrieval_topk_reference(q, bank, 4, normalize=False)
    sp, ip = retrieval_topk_pallas(q, bank, 4, normalize=False, block_q=4,
                                   block_n=16, interpret=True)
    np.testing.assert_allclose(sr, sp, atol=1e-5)
    np.testing.assert_array_equal(ir, ip)


@pytest.mark.parametrize("impl,interpret,int4,platform,want", [
    ("auto", None, True, "tpu", ("pallas", False)),
    ("auto", None, False, "tpu", ("pallas", False)),
    ("auto", None, True, "cpu", ("xla", None)),
    ("auto", None, False, "cpu", ("pallas", True)),
    ("auto", None, True, "gpu", ("xla", None)),
    ("pallas", None, True, "cpu", ("pallas", True)),
    ("pallas", False, True, "cpu", ("pallas", False)),
    ("ref", True, True, "tpu", ("ref", None)),
])
def test_resolve_impl(impl, interpret, int4, platform, want):
    """The one place a scan's backend and interpret mode are decided: the
    compiled kernel exactly on a TPU, unless the caller says otherwise."""
    assert resolve_impl(impl, interpret, int4=int4, platform=platform) == want


@pytest.mark.parametrize("impl,int4", [("ref", False), ("numpy", True)])
def test_resolve_impl_rejects_unknown(impl, int4):
    with pytest.raises(ValueError, match="unknown retrieval_topk impl"):
        resolve_impl(impl, int4=int4, platform="tpu")


# ---------------------------------------------------------------------------
# moe gemm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("T,d,E,F,bt,bf", [
    (300, 64, 8, 128, 32, 64),
    (64, 32, 4, 64, 16, 64),
    (1000, 128, 16, 256, 64, 128),
])
def test_moe_gemm_pallas_vs_ref(T, d, E, F, bt, bf):
    x = jax.random.normal(jax.random.PRNGKey(5), (T, d))
    eid = jax.random.randint(jax.random.PRNGKey(6), (T,), 0, E)
    w = jax.random.normal(jax.random.PRNGKey(7), (E, d, F)) * 0.1
    ref = moe_gemm_reference(x, eid, w)
    out = moe_gemm(x, eid, w, impl="pallas", block_t=bt, block_f=bf)
    np.testing.assert_allclose(out, ref, atol=1e-4)


def test_moe_gemm_skewed_assignment():
    """All tokens on one expert (worst-case padding plan)."""
    T, d, E, F = 128, 16, 8, 32
    x = jax.random.normal(jax.random.PRNGKey(8), (T, d))
    eid = jnp.full((T,), 3, jnp.int32)
    w = jax.random.normal(jax.random.PRNGKey(9), (E, d, F)) * 0.1
    ref = moe_gemm_reference(x, eid, w)
    out = moe_gemm(x, eid, w, impl="pallas", block_t=32, block_f=32)
    np.testing.assert_allclose(out, ref, atol=1e-4)


@pytest.mark.tier2
@settings(deadline=None, max_examples=20)
@given(st.integers(2, 6), st.integers(10, 200), st.integers(8, 64))
def test_sort_by_expert_plan_is_permutation(E, T, bt):
    eid = jnp.asarray(np.random.default_rng(E * T).integers(0, E, T))
    order, slot, block_expert, T_pad = sort_by_expert(eid, E, bt)
    assert T_pad % bt == 0
    # order is a permutation; slots are unique and within range
    assert sorted(np.asarray(order).tolist()) == list(range(T))
    slots = np.asarray(slot)
    assert len(set(slots.tolist())) == T and slots.max() < T_pad
    # every token's slot block has the right expert
    be = np.asarray(block_expert)
    e_sorted = np.asarray(eid)[np.asarray(order)]
    assert (be[slots // bt] == e_sorted).all()


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape,block", [((3, 17, 64), 16), ((128, 32), 64),
                                         ((5, 256), 8)])
def test_rmsnorm_pallas_vs_ref(shape, block):
    x = jax.random.normal(jax.random.PRNGKey(10), shape)
    s = jax.random.normal(jax.random.PRNGKey(11), (shape[-1],)) + 1.0
    np.testing.assert_allclose(rmsnorm_pallas(x, s, block_rows=block),
                               rmsnorm(x, s), atol=1e-5)
