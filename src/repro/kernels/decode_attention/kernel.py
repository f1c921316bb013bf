"""Pallas TPU flash-decoding kernel: one query vs blocked KV cache.

Grid (B, KV, nS): the S dimension is innermost/arbitrary; the per-(batch,
kv-head) accumulator (G, D) lives in VMEM across S steps. ``lengths`` rides
in SMEM. Block sizes: bkv=512 rows of K/V per step = 512*D*2 bytes each
(128KB at D=128 bf16) — two streams fit v5e VMEM comfortably while the MXU
sees (G, bkv) x (bkv, D) matmuls.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                   scale: float, window: int, block_kv: int, ns: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)        # (G, D)
    k = k_ref[0, :, 0].astype(jnp.float32)     # (bkv, D)
    v = v_ref[0, :, 0]                         # (bkv, D)
    length = len_ref[pl.program_id(0)]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (G, bkv)
    pos = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = pos < length
    if window > 0:
        valid &= pos > (length - 1 - window)
    s = jnp.where(valid, s, NEG_INF)

    m_prev, l_prev = m_ref[:, 0], l_ref[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
    m_ref[:, 0] = m_new
    l_ref[:, 0] = l_new

    @pl.when(j == ns - 1)
    def _final():
        l_safe = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)


def decode_fwd_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                      lengths: jax.Array, *, window: int = 0,
                      scale: Optional[float] = None, block_kv: int = 512,
                      interpret: bool = True) -> jax.Array:
    B, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = float(scale if scale is not None else 1.0 / np.sqrt(D))
    bkv = min(block_kv, S)
    pad = (-S) % bkv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    ns = k.shape[1] // bkv
    qg = q.reshape(B, KV, G, D)

    kernel = functools.partial(_decode_kernel, scale=scale, window=window,
                               block_kv=bkv, ns=ns)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, ns),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, j, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, bkv, 1, D), lambda b, h, j, lens: (b, j, h, 0)),
            pl.BlockSpec((1, bkv, 1, D), lambda b, h, j, lens: (b, j, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, j, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, k, v)
    return out.reshape(B, H, D)
