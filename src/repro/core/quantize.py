"""INT4 activation/embedding quantization (paper §3.4 cache analysis).

Per-row absmax scaling, two nibbles packed per int8 (TPU has no int4 compute
path — int4 here is a *storage* format; dequant happens in VMEM, see
repro.kernels.int4_cache). Pure-jnp reference lives here; it is also the
oracle for the Pallas kernel.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp


def _f32_parts(v: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Non-negative float32 ``v`` -> (biased exponent, 24-bit significand
    with the implicit bit), int32: ``v = sig * 2**(exp - 150)`` when
    normal."""
    b = jax.lax.bitcast_convert_type(v, jnp.int32)
    return b >> 23, (b & 0x7FFFFF) | 0x800000


@jax.jit
def quantize_int4(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x (..., D) with D even -> (packed (..., D//2) int8, scale (..., 1) f32).

    Bit-exact with ``quantize_int4_np`` on every backend: scale is the
    correctly rounded float32 ``max|x| / 7`` and each code is
    ``rint(fl32(x / scale))`` (half to even), clipped to [-8, 7]. Device
    division need not be correctly rounded (a TPU divides through a
    refined reciprocal; XLA may turn ``/ 7`` into ``* (1/7)``), so each
    approximate quotient is only a guess that integer arithmetic on the
    operands' significands then settles exactly."""
    assert x.shape[-1] % 2 == 0, x.shape
    xf = x.astype(jnp.float32)
    m = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    # scale: d = 7*c - m in units of c's ulp, exact in int32. m/7 rounds
    # to c iff -3 <= d <= 3 (d <= 1 when c is a power of two: its lower
    # gap is half); else step c one ulp toward m/7
    c = m / 7.0
    for _ in range(3):
        em, sm = _f32_parts(m)
        ec, sc = _f32_parts(c)
        d = 7 * sc - (sm << jnp.clip(em - ec, 0, 4))
        step = jnp.where(d < -3, 1,
                         jnp.where(d > jnp.where(sc == 0x800000, 1, 3), -1, 0))
        step = jnp.where((em > 0) & (ec > 0), step, 0)  # 0/subnormal: floored
        c = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(c, jnp.int32) + step, jnp.float32)
    scale = jnp.maximum(c, 1e-12)
    # codes: the guess q picks j with fl32(|x|/scale) in [j - .5, j + 1.5],
    # so rint only needs fl32(|x|/scale) against h = j + .5. r is
    # |x| - h*scale in units of a quarter of scale's ulp, exact in int32
    # for q in [0.3, 8). fl32 rounds to h unless |x|/scale passes h by more
    # than half the gap to h's neighbour (r > ss / 2**kp above, -r >
    # ss / 2**km below); a tie goes to h, whose low bits are zero.
    a = jnp.abs(xf)
    q = a / scale
    j = jnp.clip(jnp.floor(q), 0, 7).astype(jnp.int32)
    ea, sa = _f32_parts(a)
    es, ss = _f32_parts(scale)
    r = (sa << jnp.clip(ea - es + 2, 0, 6)) - (4 * j + 2) * ss
    kp = 20 + (j < 4) + (j < 2) + (j < 1)      # 24 - 2 - floor(log2(h))
    above = r > (ss >> kp)
    below = -r > (ss >> (kp + (j == 0)))        # 0.5 is a power of two
    tie_up = ~above & ~below & (j % 2 == 1)      # fl32 == h: to even
    mag = j + above.astype(jnp.int32) + tie_up.astype(jnp.int32)
    mag = jnp.where(q < 0.3, 0, jnp.where(q >= 8, 8, mag))
    codes = jnp.clip(jnp.where(xf < 0, -mag, mag), -8, 7).astype(jnp.int8)
    lo, hi = codes[..., 0::2], codes[..., 1::2]
    packed = (lo & jnp.int8(0x0F)) | (hi << 4)
    return packed, scale


def dequantize_int4(packed: jax.Array, scale: jax.Array,
                    dtype=jnp.float32) -> jax.Array:
    """Inverse of quantize_int4: (..., D//2) int8 -> (..., D)."""
    lo = (packed << 4) >> 4  # sign-extend low nibble (arithmetic shift on int8)
    hi = packed >> 4
    D2 = packed.shape[-1]
    out = jnp.stack([lo, hi], axis=-1).reshape(packed.shape[:-1] + (2 * D2,))
    return (out.astype(jnp.float32) * scale).astype(dtype)


def quantize_int4_np(x: "np.ndarray") -> Tuple["np.ndarray", "np.ndarray"]:
    """Pure-numpy mirror of ``quantize_int4`` — bit-exact parity (the same
    fp32 absmax/divide/round-half-even/clip rule, verified in tests). Lets
    the store quantize inserts host-side with zero device dispatches: a
    single-item ``add`` no longer pays a jit round-trip, and on accelerators
    the embedding batch never travels H2D just to come straight back."""
    xf = np.asarray(x, np.float32)
    assert xf.shape[-1] % 2 == 0, xf.shape
    scale = np.max(np.abs(xf), axis=-1, keepdims=True) / np.float32(7.0)
    scale = np.maximum(scale, np.float32(1e-12))
    q = np.clip(np.rint(xf / scale), -8, 7).astype(np.int8)
    lo, hi = q[..., 0::2], q[..., 1::2]
    packed = (lo & np.int8(0x0F)) | (hi << 4)
    return packed, scale


def dequantize_int4_np(packed: "np.ndarray", scale: "np.ndarray",
                       dtype=None) -> "np.ndarray":
    """Pure-numpy mirror of ``dequantize_int4`` (bit-exact parity)."""
    p = np.asarray(packed, np.int8)
    lo = (p << 4) >> 4  # arithmetic shift sign-extends the low nibble
    hi = p >> 4
    D2 = p.shape[-1]
    out = np.stack([lo, hi], axis=-1).reshape(p.shape[:-1] + (2 * D2,))
    out = out.astype(np.float32) * np.asarray(scale, np.float32)
    return out if dtype is None else out.astype(dtype)


def quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-row int8 (used by gradient compression)."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(xf / scale), -128, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array, dtype=jnp.float32) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)
