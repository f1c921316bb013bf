"""Plain float32 forward of one embedding tower, as the program defines it:
the block ``tower``, which every tower uses unless its configuration's
``"blocks"`` names another (``reference/<block>.py``, looked up by
``chipbench.spec.block``).

The block (departures from the published ImageBind / CLIP towers are listed
in each configuration file): tokens -> prepend a learned CLS -> add learned
positions -> per layer, pre-RMSNorm bidirectional multi-head attention
(no RoPE, no biases, softmax(QK^T / sqrt(d_head)) V) and pre-RMSNorm SwiGLU
MLP (silu(x W_gate) * (x W_up) W_down), each added to the residual stream.
The embedding at exit ``e`` is the CLS state after layer ``e`` through the
shared exit head: RMSNorm, a d_model x E projection, L2 normalisation.

A block module exports what the harness asks of every block:

  ``leaves(t, embed_dim)``     the tower's parameters, name -> (shape, init,
                               arg), drawn by ``reference/weights.py``
  ``Tower(config, seed, modality, cast=...)`` with ``run(...)`` below
  ``layer_flops(t, cfg, start, end)``, ``frontend_flops(t, cfg)``,
  ``exit_head_flops(t, cfg)``  FLOPs of one item through layers
                               [start, end), its frontend, one exit head

Every matmul runs at ``Precision.HIGHEST``. ``cast="fp8"`` is the control:
each matmul operand is rounded to float8_e4m3 with a per-tensor scale
first, the precision a later change might be tempted to serve at.
"""
from __future__ import annotations

import functools
from pathlib import Path
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from chipbench import counts
from reference import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
BENCH_DIR = Path(__file__).resolve().parents[1]   # the other blocks' home


def leaves(t: dict, embed_dim: int) -> Dict[str, Tuple[tuple, str, float]]:
    """name -> (shape, init, scale-or-fan_in) for one tower, names as the
    parameter tree spells them (``/`` between levels)."""
    L, d, h, f = t["n_layers"], t["d_model"], t["n_heads"], t["d_ff"]
    hd = d // h
    out = {
        "cls": ((1, d), "normal", 0.02),
        "exit_head/norm": ((d,), "ones", 0),
        "exit_head/proj": ((d, embed_dim), "fan_in", d),
        "final_norm": ((d,), "ones", 0),
        "layers/attn/wk": ((L, d, h, hd), "fan_in", d),
        "layers/attn/wo": ((L, h, hd, d), "fan_in", h * hd),
        "layers/attn/wq": ((L, d, h, hd), "fan_in", d),
        "layers/attn/wv": ((L, d, h, hd), "fan_in", d),
        "layers/mlp/w_down": ((L, f, d), "fan_in", f),
        "layers/mlp/w_gate": ((L, d, f), "fan_in", d),
        "layers/mlp/w_up": ((L, d, f), "fan_in", d),
        "layers/norm1": ((L, d), "ones", 0),
        "layers/norm2": ((L, d), "ones", 0),
        "pos": ((t["n_tokens"] + 1, d), "normal", 0.02),
    }
    if t["vocab"]:
        out["tok_emb"] = ((t["vocab"], d), "embed", 0.02)
    else:
        out["proj_in"] = ((t["d_input"], d), "fan_in", t["d_input"])
    return out


# -- operation counts (chipbench/counts.py has the per-layer formula) -------

def layer_flops(t: dict, cfg: dict, start: int, end: int) -> float:
    """One item through layers [start, end): every layer alike."""
    return (end - start) * counts.layer_flops(t["n_tokens"] + 1,
                                              t["d_model"], t["d_ff"])


frontend_flops = counts.frontend_flops      # the patch projection, or 0
exit_head_flops = counts.exit_head_flops    # the d_model x E projection


# -- forward ---------------------------------------------------------------

def _fp8(x):
    """Round to float8_e4m3 with a per-tensor scale, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, a, b, cast):
    if cast == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


@functools.partial(jax.jit, static_argnames=("cast", "eps"))
def _layer(x, lw, *, cast, eps):
    h = _rmsnorm(x, lw["norm1"], eps)
    q = _mm("bsd,dhk->bshk", h, lw["wq"], cast)
    k = _mm("bsd,dhk->bshk", h, lw["wk"], cast)
    v = _mm("bsd,dhk->bshk", h, lw["wv"], cast)
    s = _mm("bqhk,bshk->bhqs", q, k, cast) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("bhqs,bshk->bqhk", p, v, cast)
    x = x + _mm("bshk,hkd->bsd", o, lw["wo"], cast)
    h = _rmsnorm(x, lw["norm2"], eps)
    g = _mm("bsd,df->bsf", h, lw["w_gate"], cast)
    u = _mm("bsd,df->bsf", h, lw["w_up"], cast)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, lw["w_down"], cast)


@functools.partial(jax.jit, static_argnames=("cast", "eps"))
def _exit(pooled, norm, proj, *, cast, eps):
    e = _mm("bd,de->be", _rmsnorm(pooled, norm, eps), proj, cast)
    return e / jnp.maximum(jnp.linalg.norm(e, axis=-1, keepdims=True), 1e-8)


@functools.partial(jax.jit, static_argnames=("cast", "vocab"))
def _frontend(w, inputs, *, cast, vocab):
    if vocab:
        x = jnp.take(w["tok_emb"], inputs, axis=0, mode="clip")
    else:
        x = _mm("bsi,id->bsd", inputs.astype(jnp.float32), w["proj_in"],
                cast)
    cls = jnp.broadcast_to(w["cls"][None], (x.shape[0], 1, x.shape[-1]))
    x = jnp.concatenate([cls, x], axis=1)
    return x + w["pos"][None, :x.shape[1]]


class Tower:
    """One tower's reference forward over its weights (float32)."""

    def __init__(self, config: dict, seed: int, modality: str, *,
                 cast: str = "f32"):
        self.t = next(t for t in config["model"]["towers"]
                      if t["modality"] == modality)
        self.eps = float(config["model"]["norm_eps"])
        self.cast = cast
        self.w = W.tower_weights(config, seed, modality, BENCH_DIR)

    def _layer_weights(self, i: int) -> Dict[str, jax.Array]:
        lw = {n.split("/")[-1]: a[i] for n, a in self.w.items()
              if n.startswith("layers/")}
        return lw

    def run(self, *, inputs=None, h_state=None, start: int = 0,
            end: Optional[int] = None, exits=(), keep_h_at=None):
        """Layers [start, end) from the frontend (``inputs``) or from a
        hidden state (``h_state``). Returns ({exit layer: (B, E) embedding}
        for each exit in ``exits`` within (start, end], hidden state after
        layer ``keep_h_at`` or None)."""
        end = self.t["n_layers"] if end is None else end
        if h_state is None:
            x = _frontend(self.w, jnp.asarray(inputs), cast=self.cast,
                          vocab=int(self.t["vocab"]))
        else:
            x = jnp.asarray(h_state, jnp.float32)
        embs, kept = {}, None
        for i in range(start, end):
            x = _layer(x, self._layer_weights(i), cast=self.cast,
                       eps=self.eps)
            if i + 1 in exits:
                embs[i + 1] = np.asarray(_exit(
                    x[:, 0], self.w["exit_head/norm"],
                    self.w["exit_head/proj"], cast=self.cast, eps=self.eps))
            if keep_h_at == i + 1:
                kept = np.asarray(x)
        return embs, kept
