"""Pallas TPU flash-attention forward kernel.

Grid (B, H, nq, nkv); the kv dimension is the innermost ("arbitrary")
dimension so the VMEM accumulator persists across kv steps. Blocks are sized
for v5e VMEM (~128KB working set per step at bq=bkv=256, D=128, fp32 acc) and
MXU alignment (multiples of 128 on the contracting/lane dims).

On CPU this runs under ``interpret=True`` (tests); real-hardware dispatch is
handled by ops.flash_attention(impl="pallas").
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                scale: float, causal: bool, window: int, q_offset: int,
                skv_real: int, sq_real: int, block_q: int, block_kv: int,
                nkv: int):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)  # (bkv, D)
    v = v_ref[0, 0]                      # (bkv, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0) + q_offset
    kpos = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    mask = (kpos < skv_real) & ((qpos - q_offset) < sq_real)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > (qpos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, 0]
    l_prev = l_ref[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
    m_ref[:, 0] = m_new
    l_ref[:, 0] = l_new

    @pl.when(j == nkv - 1)
    def _final():
        l_safe = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[:, 0] + jnp.log(l_safe)


def flash_fwd_pallas(cfg, q, k, v, *, interpret: bool = True
                     ) -> Tuple[jax.Array, jax.Array]:
    """q: (B,KV,G,Sq,D) grouped layout (see ops.py); returns (out, lse)."""
    B, KV, G, Sq, D = q.shape
    Skv = k.shape[2]
    bq, bkv = cfg.block_q, cfg.block_kv
    nq, nkv = Sq // bq, Skv // bkv
    H = KV * G
    qf = q.reshape(B, H, Sq, D)

    kernel = functools.partial(
        _fwd_kernel, scale=cfg.scale, causal=cfg.causal, window=cfg.window,
        q_offset=cfg.q_offset, skv_real=cfg.skv_real, sq_real=cfg.sq_real,
        block_q=bq, block_kv=bkv, nkv=nkv)

    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, h, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qf, k.reshape(B, KV, Skv, D), v.reshape(B, KV, Skv, D))
    return out.reshape(B, KV, G, Sq, D), lse.reshape(B, KV, G, Sq)
