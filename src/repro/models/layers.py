"""Foundational layers: schema-driven params, norms, RoPE, GQA attention, MLPs.

Design notes
------------
* Pure-functional: ``init`` builds a pytree of arrays from a *schema*; the same
  schema yields the logical-axis PartitionSpec pytree, so parameter structure
  and sharding can never drift apart (tested in tests/test_layers.py).
* Layers are written against the XLA reference path. Pallas kernels (see
  repro.kernels) are swapped in by ops-level dispatch where profitable.
* Activation sharding constraints go through
  :func:`repro.distributed.mesh_utils.shard_activation` which is a no-op
  outside a mesh context, so every model runs unmodified on one CPU device.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.mesh_utils import shard_activation

# ---------------------------------------------------------------------------
# Schema-driven parameters
# ---------------------------------------------------------------------------


class ParamDef(NamedTuple):
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]  # logical axis names, len == len(shape)
    init: str = "normal"  # normal | zeros | ones | embed | fan_in
    scale: float = 1.0
    # "fan_in" init: the contracted input size; 0 = shape[-2], right for a
    # plain (..., in, out) matrix but not for head-split attention weights
    fan_in: int = 0


Schema = Dict[str, Any]  # nested dict of ParamDef


def _init_leaf(key: jax.Array, d: ParamDef, dtype) -> jax.Array:
    if d.init == "zeros":
        return jnp.zeros(d.shape, dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, dtype)
    if d.init == "normal":
        return (d.scale * jax.random.normal(key, d.shape)).astype(dtype)
    if d.init == "embed":
        return (d.scale * jax.random.normal(key, d.shape) * 0.02).astype(dtype)
    if d.init == "fan_in":
        fan_in = d.fan_in or (d.shape[-2] if len(d.shape) >= 2
                              else d.shape[-1])
        std = d.scale / np.sqrt(fan_in)
        return (std * jax.random.normal(key, d.shape)).astype(dtype)
    raise ValueError(d.init)


def init_params(key: jax.Array, schema: Schema, dtype=jnp.float32):
    """Initialize a nested param pytree from a schema."""
    leaves, treedef = jax.tree.flatten(schema, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, len(leaves))
    out = [_init_leaf(k, d, dtype) for k, d in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, out)


def param_specs(schema: Schema):
    """Logical-axes pytree matching :func:`init_params` output structure."""
    return jax.tree.map(lambda d: d.axes, schema,
                        is_leaf=lambda x: isinstance(x, ParamDef))


def abstract_params(schema: Schema, dtype=jnp.float32):
    """ShapeDtypeStruct pytree (for dry-run lowering without allocation)."""
    return jax.tree.map(lambda d: jax.ShapeDtypeStruct(d.shape, dtype), schema,
                        is_leaf=lambda x: isinstance(x, ParamDef))


def count_params(params) -> int:
    return int(sum(np.prod(x.shape) for x in jax.tree.leaves(params)))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_schema(d: int, layer_dims: Tuple[int, ...] = ()) -> ParamDef:
    axes = tuple("layer" for _ in layer_dims) + ("embed",)
    return ParamDef(layer_dims + (d,), axes, "ones")


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(dt)


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)  # (head_dim//2,)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    freqs = rope_frequencies(x.shape[-1], theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    sin = jnp.sin(angles)[..., None, :]  # (..., seq, 1, hd/2)
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (reference / XLA path; Pallas kernels live in repro.kernels)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def attention_scores_mask(q_len: int, kv_len: int, *, causal: bool,
                          window: int = 0, q_offset: int = 0) -> jax.Array:
    """(q_len, kv_len) bool mask; True = attend."""
    qi = jnp.arange(q_len)[:, None] + q_offset
    kj = jnp.arange(kv_len)[None, :]
    mask = jnp.ones((q_len, kv_len), dtype=bool)
    if causal:
        mask &= kj <= qi
    if window and window > 0:
        mask &= kj > (qi - window)
    return mask


def multihead_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        mask: Optional[jax.Array] = None,
                        scale: Optional[float] = None) -> jax.Array:
    """Grouped-query attention, reference path.

    q: (B, Sq, H, D); k, v: (B, Skv, KV, D) with H % KV == 0.
    mask: broadcastable to (B, H, Sq, Skv) or (Sq, Skv); True = attend.
    """
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    qg = q.reshape(B, Sq, KV, G, D)
    # scores: (B, KV, G, Sq, Skv)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if mask is not None:
        if mask.ndim == 2:
            m = mask[None, None, None]
        elif mask.ndim == 3:  # (B, Sq, Skv)
            m = mask[:, None, None]
        else:  # (B, H, Sq, Skv)
            m = mask.reshape(B, KV, G, Sq, -1)
        scores = jnp.where(m, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def attn_schema(d_model: int, n_heads: int, n_kv: int, head_dim: int,
                qkv_bias: bool, layer_dims: Tuple[int, ...] = ()) -> Schema:
    L = layer_dims
    la = tuple("layer" for _ in L)
    s: Schema = {
        "wq": ParamDef(L + (d_model, n_heads, head_dim), la + ("embed", "heads", "head_dim"), "fan_in", fan_in=d_model),
        "wk": ParamDef(L + (d_model, n_kv, head_dim), la + ("embed", "kv_heads", "head_dim"), "fan_in", fan_in=d_model),
        "wv": ParamDef(L + (d_model, n_kv, head_dim), la + ("embed", "kv_heads", "head_dim"), "fan_in", fan_in=d_model),
        "wo": ParamDef(L + (n_heads, head_dim, d_model), la + ("heads", "head_dim", "embed"), "fan_in", fan_in=n_heads * head_dim),
    }
    if qkv_bias:
        s["bq"] = ParamDef(L + (n_heads, head_dim), la + ("heads", "head_dim"), "zeros")
        s["bk"] = ParamDef(L + (n_kv, head_dim), la + ("kv_heads", "head_dim"), "zeros")
        s["bv"] = ParamDef(L + (n_kv, head_dim), la + ("kv_heads", "head_dim"), "zeros")
    return s


def attn_project_qkv(p: Schema, x: jax.Array, *, rope_theta: float,
                     positions: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, d_model) -> q (B,S,H,D), k/v (B,S,KV,D), RoPE applied."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if rope_theta > 0:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    return q, k, v


def attn_output(p: Schema, o: jax.Array) -> jax.Array:
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def swiglu_schema(d_model: int, d_ff: int, layer_dims: Tuple[int, ...] = ()) -> Schema:
    L = layer_dims
    la = tuple("layer" for _ in L)
    return {
        "w_gate": ParamDef(L + (d_model, d_ff), la + ("embed", "mlp"), "fan_in"),
        "w_up": ParamDef(L + (d_model, d_ff), la + ("embed", "mlp"), "fan_in"),
        "w_down": ParamDef(L + (d_ff, d_model), la + ("mlp", "embed"), "fan_in"),
    }


def swiglu(p: Schema, x: jax.Array) -> jax.Array:
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    h = shard_activation(h, ("batch", "seq", "mlp"))
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(x.dtype))


def mlp_schema(dims: Sequence[int], name_axes: Tuple[str, str] = ("embed", "mlp"),
               bias: bool = True) -> Schema:
    """Plain feed-forward stack ``dims[0] -> dims[1] -> ... -> dims[-1]``."""
    s: Schema = {}
    for i in range(len(dims) - 1):
        s[f"w{i}"] = ParamDef((dims[i], dims[i + 1]), name_axes, "fan_in")
        if bias:
            s[f"b{i}"] = ParamDef((dims[i + 1],), (name_axes[1],), "zeros")
    return s


def mlp_apply(p: Schema, x: jax.Array, *, act=jax.nn.relu,
              final_act: bool = False) -> jax.Array:
    n = len([k for k in p if k.startswith("w")])
    for i in range(n):
        x = x @ p[f"w{i}"].astype(x.dtype)
        if f"b{i}" in p:
            x = x + p[f"b{i}"].astype(x.dtype)
        if i < n - 1 or final_act:
            x = act(x)
    return x


# ---------------------------------------------------------------------------
# Embedding / misc
# ---------------------------------------------------------------------------


def embed_schema(vocab: int, d: int) -> ParamDef:
    return ParamDef((vocab, d), ("vocab", "embed"), "embed")


def embed_lookup(table: jax.Array, ids: jax.Array) -> jax.Array:
    return jnp.take(table, ids, axis=0, mode="clip")


def segment_sum(data: jax.Array, segment_ids: jax.Array, num_segments: int) -> jax.Array:
    return jax.ops.segment_sum(data, segment_ids, num_segments=num_segments)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token cross-entropy; logits (..., V), labels (...) int32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def l2_normalize(x: jax.Array, eps: float = 1e-8) -> jax.Array:
    n = jnp.linalg.norm(x.astype(jnp.float32), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) / jnp.maximum(n, eps)).astype(x.dtype)
