"""Distributed training driver with fault tolerance.

Wires together: step builders (launch/steps), checkpoint manager (atomic +
async + retention), elastic restore (any checkpoint -> current mesh),
straggler monitor, and the data pipeline. Runs for real at smoke scale on
CPU (examples/ and tests use it); at pod scale the same loop lowers through
the dry-run artifacts.

Usage (smoke):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \
      --steps 20 --ckpt-dir /tmp/ck
"""
from __future__ import annotations

import argparse
import os
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import CheckpointManager
from repro.configs.base import get_arch, smoke_variant
from repro.models import gnn as G
from repro.models import imagebind as IB
from repro.models import recsys as R
from repro.models import transformer as T
from repro.data import synthetic as SYN
from repro.data.pipeline import ShardedLoader
from repro.distributed.mesh_utils import sharding_ctx
from repro.distributed.straggler import Action, StragglerMonitor
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_step


def make_train_data(spec, shape, n: int, seed: int = 0) -> Dict[str, np.ndarray]:
    if spec.family == "lm":
        toks = SYN.lm_tokens(seed, n, shape.seq_len + 1, spec.model.vocab)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    if spec.family == "recsys":
        if spec.model.kind == "dlrm":
            return SYN.criteo_like(seed, n, spec.model)
        return SYN.seq_recsys(seed, n, spec.model)
    if spec.family == "mem":
        md = SYN.multimodal_pairs(seed, n, spec.model)
        return dict(md.items)
    raise ValueError(spec.family)


def train_loop(spec, shape, *, mesh=None, multi_pod: bool = False,
               steps: int = 50, ckpt_dir: Optional[str] = None,
               save_interval: int = 20, n_data: int = 512,
               log_every: int = 10, resume: bool = True,
               seed: int = 0) -> Dict[str, Any]:
    """Build, (maybe) restore, and run the train step for `steps` steps."""
    if mesh is None:
        mesh = make_mesh((1, 1), ("data", "model"))
    shape_cfg = spec.shape(shape) if isinstance(shape, str) else shape
    bundle = build_step(spec, shape_cfg, mesh, multi_pod=multi_pod)

    # materialize params (proper per-family init) + zero opt state
    key = jax.random.PRNGKey(seed)
    if spec.family == "lm":
        params = T.lm_init(key, spec.model, spec.recall)
    elif spec.family == "gnn":
        from dataclasses import replace as _rp
        cfg_g = _rp(spec.model, d_feat=shape_cfg.d_feat or spec.model.d_feat)
        params = G.gnn_init(key, cfg_g, spec.recall,
                            embed_out=min(1024, cfg_g.d_hidden * 8))
    elif spec.family == "recsys":
        params = R.recsys_init(key, spec.model)
    else:
        params = IB.mem_init(key, spec.model, spec.recall)
    with sharding_ctx(mesh, bundle.rules):
        params = jax.tree.map(lambda x, sh: jax.device_put(x, sh),
                              params, bundle.in_shardings[0])
        opt_state = jax.tree.map(
            lambda ab: jnp.zeros(ab.shape, ab.dtype), bundle.abstract_args[1])

    mgr = None
    start_step = 0
    loader_state = None
    if ckpt_dir:
        mgr = CheckpointManager(ckpt_dir, save_interval=save_interval)
        if resume:
            restored, manifest = mgr.restore_or_none({"params": params,
                                                      "opt": opt_state})
            if restored is not None:
                params, opt_state = restored["params"], restored["opt"]
                start_step = manifest["step"]
                loader_state = manifest["meta"].get("loader")
                print(f"[train] resumed from step {start_step}")

    data = make_train_data(spec, shape_cfg, n_data, seed)
    loader = ShardedLoader(data, global_batch=shape_cfg.global_batch, seed=seed)
    if loader_state:
        loader.load_state_dict(loader_state)

    jitted = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                     out_shardings=bundle.out_shardings,
                     donate_argnums=bundle.donate_argnums)
    monitor = StragglerMonitor(n_hosts=1, warmup=3)
    it = iter(loader)
    losses = []
    with sharding_ctx(mesh, bundle.rules):
        for step in range(start_step, start_step + steps):
            batch = next(it)
            batch = {k: jnp.asarray(v) for k, v in batch.items()
                     if k in bundle.abstract_args[2]}
            t0 = time.perf_counter()
            params, opt_state, metrics = jitted(params, opt_state, batch)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            losses.append(loss)
            decision = monitor.record(np.array([dt]))
            if decision.action == Action.RESTART_WITHOUT_HOST and mgr:
                mgr.save(step, {"params": params, "opt": opt_state},
                         meta={"loader": loader.state_dict()}, blocking=True)
            if log_every and (step % log_every == 0):
                print(f"[train] step {step} loss {loss:.4f} ({dt*1e3:.0f} ms)")
            if mgr and mgr.should_save(step):
                mgr.save(step, {"params": params, "opt": opt_state},
                         meta={"loader": loader.state_dict()})
    if mgr:
        mgr.save(start_step + steps, {"params": params, "opt": opt_state},
                 meta={"loader": loader.state_dict()}, blocking=True)
        mgr.ckpt.wait()
    return {"params": params, "opt_state": opt_state, "losses": losses,
            "final_step": start_step + steps}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced CPU-runnable variant")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-interval", type=int, default=20)
    ap.add_argument("--n-data", type=int, default=512)
    args = ap.parse_args()

    spec = get_arch(args.arch)
    if args.smoke:
        spec = smoke_variant(spec)
    shape = args.shape or next(s.name for s in spec.shapes if s.kind == "train")
    out = train_loop(spec, shape, steps=args.steps, ckpt_dir=args.ckpt_dir,
                     save_interval=args.save_interval, n_data=args.n_data)
    print(f"final loss: {out['losses'][-1]:.4f} "
          f"(first {out['losses'][0]:.4f}) @ step {out['final_step']}")


if __name__ == "__main__":
    main()
