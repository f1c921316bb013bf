"""Pallas TPU grouped ("ragged") expert GEMM — MegaBlocks-style, TPU-adapted.

Tokens arrive *sorted by expert* with each group padded to the token-block
size (ops.py does the sort/pad). The per-block expert id rides in as a
scalar-prefetch array and drives the *index map* of the weight operand: block
i of the token dim loads w[block_expert[i]] — so each expert's weights are
streamed from HBM exactly once per contiguous group, and the MXU sees dense
(bt, d) x (d, bf) tiles. This is the TPU translation of MegaBlocks'
block-sparse GEMM (no dynamic shapes, no gather in the inner loop).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _gemm_kernel(be_ref, x_ref, w_ref, o_ref):
    x = x_ref[...]          # (bt, d)
    w = w_ref[0]            # (d, bf)
    o_ref[...] = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def moe_gemm_pallas(x: jax.Array, block_expert: jax.Array, w: jax.Array, *,
                    block_t: int = 256, block_f: int = 512,
                    interpret: bool = True) -> jax.Array:
    """x (Tp, d) tokens sorted+padded by expert; block_expert (Tp//bt,) int32;
    w (E, d, f) -> (Tp, f)."""
    Tp, d = x.shape
    E, _, F = w.shape
    bt = block_t
    bf = min(block_f, F)
    assert Tp % bt == 0, (Tp, bt)
    assert F % bf == 0, (F, bf)
    nt, nf = Tp // bt, F // bf

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt, nf),
        in_specs=[
            pl.BlockSpec((bt, d), lambda i, j, be: (i, 0)),
            pl.BlockSpec((1, d, bf), lambda i, j, be: (be[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((bt, bf), lambda i, j, be: (i, j)),
    )
    return pl.pallas_call(
        _gemm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tp, F), x.dtype),
        interpret=interpret,
    )(block_expert.astype(jnp.int32), x, w)
