"""Pallas TPU kernels: per-row INT4 quantize / dequantize for the activation
cache (paper §3.4).

TPU has no int4 compute — int4 is a *storage* format here: nibbles are packed
two-per-int8 in VMEM right before the HBM write (quantize) and unpacked right
after the HBM read (dequantize). Row blocks of 256 keep the f32 staging
buffer at 256*D*4 bytes (128KB at D=128) per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _quant_kernel(x_ref, packed_ref, scale_ref):
    x = x_ref[...].astype(jnp.float32)  # (bn, D)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax / 7.0, 1e-12)
    q = jnp.clip(jnp.round(x / scale), -8, 7).astype(jnp.int8)
    bn, D = q.shape
    pair = q.reshape(bn, D // 2, 2)
    lo, hi = pair[..., 0], pair[..., 1]
    packed_ref[...] = (lo & jnp.int8(0x0F)) | (hi << 4)
    scale_ref[...] = scale


def _dequant_kernel(packed_ref, scale_ref, x_ref):
    p = packed_ref[...]  # (bn, D//2) int8
    lo = (p << 4) >> 4   # arithmetic shift sign-extends the low nibble
    hi = p >> 4
    bn, D2 = p.shape
    out = jnp.stack([lo, hi], axis=-1).reshape(bn, 2 * D2)
    x_ref[...] = (out.astype(jnp.float32) * scale_ref[...]).astype(x_ref.dtype)


def quantize_int4_pallas(x: jax.Array, *, block_rows: int = 256,
                         interpret: bool = True):
    """x (N, D), D even -> (packed (N, D//2) int8, scale (N, 1) f32)."""
    N, D = x.shape
    bn = min(block_rows, N)
    pad = (-N) % bn
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    n = x.shape[0] // bn
    packed, scale = pl.pallas_call(
        _quant_kernel,
        grid=(n,),
        in_specs=[pl.BlockSpec((bn, D), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bn, D // 2), lambda i: (i, 0)),
                   pl.BlockSpec((bn, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((x.shape[0], D // 2), jnp.int8),
                   jax.ShapeDtypeStruct((x.shape[0], 1), jnp.float32)],
        interpret=interpret,
    )(x)
    return packed[:N], scale[:N]


def dequantize_int4_pallas(packed: jax.Array, scale: jax.Array, *,
                           dtype=jnp.float32, block_rows: int = 256,
                           interpret: bool = True) -> jax.Array:
    N, D2 = packed.shape
    bn = min(block_rows, N)
    pad = (-N) % bn
    if pad:
        packed = jnp.pad(packed, ((0, pad), (0, 0)))
        scale = jnp.pad(scale, ((0, pad), (0, 0)))
    n = packed.shape[0] // bn
    x = pl.pallas_call(
        _dequant_kernel,
        grid=(n,),
        in_specs=[pl.BlockSpec((bn, D2), lambda i: (i, 0)),
                  pl.BlockSpec((bn, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bn, 2 * D2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((packed.shape[0], 2 * D2), dtype),
        interpret=interpret,
    )(packed, scale)
    return x[:N]
