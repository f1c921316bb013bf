"""Every tower's weights from the seed, by the documented initialisation.

One PRNG key per parameter, split from ``PRNGKey(seed)`` in the order the
program flattens its parameter tree: ``logit_scale``, then each tower by
modality name, each tower's leaves by their ``/``-split names (nested dicts
flatten by sorted key at every level). A tower's leaves come from its own
block (``reference/<block>.py``, ``leaves(t, embed_dim)``), so a tower of
another block moves every key after it exactly as it does in the program.
Each leaf is drawn in float32 and rounded to the served dtype.
"""
from __future__ import annotations

import functools
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from chipbench import spec


def leaf_order(config: dict, bench_dir: Path
               ) -> List[Tuple[str, str, tuple, str, float]]:
    """Every parameter in key order: (modality, leaf name, shape, init, arg);
    ``logit_scale`` first, under modality ''. Each tower's block is looked
    up under ``bench_dir``."""
    m = config["model"]
    out = [("", "logit_scale", (), "zeros", 0)]
    for t in sorted(m["towers"], key=lambda t: t["modality"]):
        leaves = spec.block(config, t["modality"],
                            bench_dir=bench_dir).leaves(t, m["embed_dim"])
        for name in sorted(leaves, key=lambda n: n.split("/")):
            out.append((t["modality"], name) + leaves[name])
    return out


@functools.partial(jax.jit, static_argnames=("shape", "init", "arg", "dtype"))
def _draw(key, *, shape, init, arg, dtype):
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "normal":
        return (arg * jax.random.normal(key, shape)).astype(dtype)
    if init == "embed":
        return (1.0 * jax.random.normal(key, shape) * arg).astype(dtype)
    std = 1.0 / np.sqrt(arg)
    return (std * jax.random.normal(key, shape)).astype(dtype)


def tower_weights(config: dict, seed: int, modality: str, bench_dir: Path
                  ) -> Dict[str, jax.Array]:
    """One tower's weights from the seed, in the served dtype, as float32."""
    order = leaf_order(config, bench_dir)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(order))
    dtype = config["model"]["dtype"]
    out = {}
    for key, (mod, name, shape, init, arg) in zip(keys, order):
        if mod == modality:
            out[name] = _draw(key, shape=shape, init=init, arg=arg,
                              dtype=dtype).astype(jnp.float32)
    return out
