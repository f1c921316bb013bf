"""Serving driver: embedding runtime + query runtime, end-to-end.

Queries are served through ``QueryEngine.query_batch`` (one tower pass +
one fused store scan for the whole query drain); ``--per-query`` falls back
to the sequential seed-style loop.

Smoke-scale on CPU:
  PYTHONPATH=src python -m repro.launch.serve --smoke --n-items 128 --n-queries 16
"""
from __future__ import annotations

import argparse
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_arch, smoke_variant
from repro.core import exits as EX
from repro.core import preexit as PE
from repro.core.store import EmbeddingStore
from repro.data import synthetic as SYN
from repro.models import imagebind as IB
from repro.serving.engine import EmbeddingEngine
from repro.serving.query import QueryEngine


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process; call it
    from an entry point's ``main()`` before anything compiles. A set
    ``JAX_COMPILATION_CACHE_DIR`` is used as it is (JAX reads it itself);
    otherwise the cache lives at ``<checkout>/.jax_cache``, a fixed path,
    since the path is part of what a later run must find again. Returns
    the directory in use."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(Path(__file__).resolve().parents[3] / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def build_service(spec, *, n_train: int = 256, seed: int = 0, policy="recall",
                  params=None, lora=None, fw_kw=None, search_impl="auto",
                  search_devices=None, bank_refresh="sync",
                  bank_max_lag_rows=None, bank_max_lag_ms=None,
                  index="none", index_clusters=64, index_min_rows=None,
                  nprobe=None, index_auto_grow=False):
    """Train the pre-exit predictor from self-supervised labels, then stand up
    the embedding + query engines."""
    cfg, recall = spec.model, spec.recall
    key = jax.random.PRNGKey(seed)
    if params is None:
        # one compiled init instead of an eager compile per weight shape
        params = jax.jit(IB.mem_init, static_argnums=(1, 2))(key, cfg, recall)
    fw_kw = fw_kw or {}
    data = SYN.multimodal_pairs(seed, n_train, cfg)
    vis = jnp.asarray(data.items["vision"])

    # self-supervised exit labels on a calibration split (jitted with the
    # weights as arguments: eager, every op compiles on its own)
    exit_embs = jax.jit(lambda p, lo, x: IB.mem_embed_all_exits(
        p, cfg, recall, "vision", x, lora=lo, **fw_kw)["exit_embs"])(
            params, lora, vis)
    labels = EX.optimal_exit_labels(exit_embs, exit_embs[-1])
    sup = jax.jit(lambda p, lo, x: IB.tower_forward(
        p, cfg, recall, "vision", x, layer_end=recall.superficial_layers,
        lora=lo, **fw_kw)["pooled"][-1])(params, lora, vis)
    predictor, stats = PE.train_predictor(
        key, sup, labels, n_exits=len(recall.exit_layers(
            cfg.tower("vision").n_layers)),
        hidden=recall.predictor_hidden, steps=150)

    store = EmbeddingStore(cfg.embed_dim)
    engine = EmbeddingEngine(params, cfg, recall, modality="vision", lora=lora,
                             predictor_params=predictor, policy=policy,
                             store=store, fw_kw=fw_kw)
    query = QueryEngine(params, cfg, recall, store=store,
                        refine_fn=engine.refine_fn(), query_modality="text",
                        lora=lora, fw_kw=fw_kw, search_impl=search_impl,
                        search_devices=search_devices,
                        bank_refresh=bank_refresh,
                        bank_max_lag_rows=bank_max_lag_rows,
                        bank_max_lag_ms=bank_max_lag_ms,
                        index=index, index_clusters=index_clusters,
                        index_min_rows=index_min_rows, nprobe=nprobe,
                        index_auto_grow=index_auto_grow)
    return engine, query, {"predictor": stats, "labels": np.asarray(labels)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="recall-imagebind")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--n-items", type=int, default=128)
    ap.add_argument("--n-queries", type=int, default=16)
    ap.add_argument("--policy", default="recall",
                    choices=["recall", "branchynet", "fixed", "full"])
    ap.add_argument("--per-query", action="store_true",
                    help="serve queries one at a time instead of one "
                         "query_batch drain")
    ap.add_argument("--search-impl", default="auto",
                    choices=["auto", "numpy", "pallas", "xla", "device",
                             "ivf"],
                    help="store scan backend; 'device' keeps the int4 slab "
                         "resident on device (auto picks it on accelerators) "
                         "and shards it across --search-shards devices; "
                         "'ivf' forces the pruned coarse-filter scan, "
                         "shard-routed when the bank spans devices "
                         "(needs --index ivf; on accelerators auto picks "
                         "it past --index-min-rows, on CPU only this "
                         "explicit choice uses it)")
    ap.add_argument("--search-shards", type=int, default=0,
                    help="shard the device bank across this many devices "
                         "(0 = all local devices when --search-impl=device)")
    ap.add_argument("--bank-refresh", default="sync",
                    choices=["sync", "async"],
                    help="device-bank refresh policy: 'sync' refreshes "
                         "exactly under the store lock per query; 'async' "
                         "scatters dirty rows on a background scheduler and "
                         "serves bounded-stale snapshots")
    ap.add_argument("--bank-max-lag", type=int, default=None,
                    help="async only: max dirty-but-unpublished ROWS before "
                         "a query blocks for a refresh (default unbounded; "
                         "0 = fresh-blocking)")
    ap.add_argument("--bank-max-lag-ms", type=float, default=None,
                    help="async only: max age in ms of the oldest "
                         "unpublished write before a query blocks")
    ap.add_argument("--index", default="none", choices=["none", "ivf"],
                    help="coarse-filter index: 'ivf' maintains an online "
                         "mini-batch-k-means quantizer + posting lists and "
                         "serves queries by pruned (top-nprobe clusters) "
                         "scan once the store passes --index-min-rows")
    ap.add_argument("--index-clusters", type=int, default=64,
                    help="IVF cluster count (coarse codebook size)")
    ap.add_argument("--index-min-rows", type=int, default=None,
                    help="row count where search impl='auto' cuts over to "
                         "the pruned IVF path (default: the index's "
                         "32768; small demos want a lower value)")
    ap.add_argument("--nprobe", type=int, default=None,
                    help="IVF clusters probed per query (default: the "
                         "index's 8; higher = better recall, more scan)")
    ap.add_argument("--index-auto-grow", action="store_true",
                    help="grow the IVF cluster count toward ~sqrt(n) "
                         "across re-cluster epochs instead of pinning the "
                         "--index-clusters choice (keeps the probed "
                         "fraction sub-linear as the store scales)")
    args = ap.parse_args()
    enable_compile_cache()

    spec = get_arch(args.arch)
    if args.smoke:
        spec = smoke_variant(spec)
    devices = None
    if args.search_impl == "device" and args.search_shards:
        devices = jax.devices()[:args.search_shards]
    engine, query, info = build_service(spec, policy=args.policy,
                                        search_impl=args.search_impl,
                                        search_devices=devices,
                                        bank_refresh=args.bank_refresh,
                                        bank_max_lag_rows=args.bank_max_lag,
                                        bank_max_lag_ms=args.bank_max_lag_ms,
                                        index=args.index,
                                        index_clusters=args.index_clusters,
                                        index_min_rows=args.index_min_rows,
                                        nprobe=args.nprobe,
                                        index_auto_grow=args.index_auto_grow)
    print(f"predictor: {info['predictor']}")

    data = SYN.multimodal_pairs(1, args.n_items, spec.model)
    t0 = time.perf_counter()
    engine.submit_batch(np.arange(args.n_items), data.items["vision"])
    stats = engine.drain()
    print(f"embedded {stats.n_embedded} items, avg layers "
          f"{stats.avg_layers:.1f}/{spec.model.tower('vision').n_layers}, "
          f"{stats.n_embedded / stats.wall_s:.1f} items/s (host wall)")
    print(f"store: {engine.store.storage_bytes()}")

    nq = min(args.n_queries, len(data.items["text"]))
    t0 = time.perf_counter()
    if args.per_query:
        results = [query.query(data.items["text"][qi], k=10)
                   for qi in range(nq)]
    else:
        results = query.query_batch(data.items["text"][:nq], k=10)
    dt = time.perf_counter() - t0
    hits = sum(int(len(r.uids) > 0 and r.uids[0] == qi)
               for qi, r in enumerate(results))
    mode = "per-query" if args.per_query else "batched"
    print(f"{nq} {mode} queries in {dt:.2f}s "
          f"({dt / nq * 1e3:.0f} ms/query host), "
          f"{sum(r.n_refined for r in results)} refinements")
    print(f"R@1 (untrained model, sanity only): {hits / nq:.2f}")
    if engine.store.device_bank is not None:
        print(f"device bank: {engine.store.device_bank.stats()}")
    if engine.store.ivf_index is not None:
        print(f"ivf index: {engine.store.ivf_index.stats()}, "
              f"fallbacks={engine.store.ivf_fallbacks}")
    ref = engine.store.bank_refresher
    if ref is not None:
        print(f"bank refresh: async, epochs={ref.n_epochs}, "
              f"blocking={ref.n_blocking}, stale={ref.n_stale_served}, "
              f"lag={ref.lag()}")
        engine.store.set_bank_refresh("sync")  # drain + stop the thread


if __name__ == "__main__":
    main()
