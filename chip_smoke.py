#!/usr/bin/env python3
"""Chip smoke: the RECALL serving path, once, on a TPU, at published widths.

One chip (default). Through the entry points a deployment calls, at the
full ``recall-imagebind`` widths (vision 32L/1280, text 24L/1024, 1024-d
space, bf16), with random weights and data made from ``--seed``:

  1. set-up   ``launch.serve.build_service`` on one chip: weights, pre-exit
              predictor, engines, the device-resident int4 bank;
  2. bank     seeded rows through ``store.add_batch`` until the bank holds
              65,536 rows, so a scan crosses many kernel blocks;
  3. ingest   one ``submit_batch`` + ``drain`` of 64 vision items;
  4. query    64 text queries (the config's ``query_batch``) through
              ``query_batch``: round 1 scans at Q = 64 x 3 granularities;
  5. checks   the scan is the compiled Pallas kernel; the round-1 device
              scan agrees with the numpy scan of the same store; round 3
              refined candidates (layers 7 -> 32 ran on the chip); the bf16
              text tower agrees with itself in float32.

``--four-chips`` runs only the sharded bank: the exhaustive scan and the
shard-routed IVF union scan over 4 chips, each against the same store's
bank on one chip.

Times printed are smoke timings of one run, compilation included, not
benchmark numbers. The last line of stdout is the JSON result. Without a
TPU, or when any check fails, the script exits non-zero and prints no
result.

Run from the checkout root:  python chip_smoke.py [--four-chips]
The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.base import get_arch  # noqa: E402
from repro.core.store import EmbeddingStore  # noqa: E402
from repro.data import synthetic as SYN  # noqa: E402
from repro.launch.serve import build_service, enable_compile_cache  # noqa: E402
from repro.models import imagebind as IB  # noqa: E402

ARCH = "recall-imagebind"
BANK_ROWS = 65_536
N_ITEMS = 64
K = 10
# Background rows are seeded directions at this norm, so they score at
# most ~0.05 x 0.15 against a unit query: a query's best ingested items
# then reach its top 10 and round 3 has candidates to refine. At unit norm
# the 64 ingested items would be buried among 65k random rows.
BG_NORM = 0.05
# int4 rows are small integers times a row scale, so a score's only error
# is the query's rounding inside the MXU: at worst bf16's 2^-9 relative per
# term, ~1e-4 absolute on these unit-norm scores. 1e-3 leaves 10x room.
SCORE_ATOL = 1e-3
# Same arithmetic on 1 and 4 chips: scores of a row must agree closely.
SHARD_ATOL = 1e-5
# bf16 against float32 at "highest" matmul precision through 24 layers:
# bf16 keeps 8 bits of mantissa, and each layer's rounding is a relative
# ~2^-9 error; cosine 0.99 allows an angle of 8 degrees.
COS_MIN = 0.99


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def topk_agree(ua, sa, ub, sb, atol: float):
    """(max |score diff|, rows whose uid sets differ only by ties). Rows of
    two top-k results agree when their scores match within ``atol`` and
    every uid in one set but not the other scores within ``atol`` of the
    k-th score (a tie at the boundary)."""
    check(ua.shape == ub.shape, f"shapes differ: {ua.shape} vs {ub.shape}")
    max_diff = float(np.max(np.abs(np.sort(sa, 1) - np.sort(sb, 1))))
    check(max_diff <= atol, f"top-k scores differ by {max_diff} > {atol}")
    ties = 0
    for r in range(len(ua)):
        a, b = set(ua[r].tolist()), set(ub[r].tolist())
        if a == b:
            continue
        kth = min(sa[r].min(), sb[r].min())
        score = {**dict(zip(ua[r].tolist(), sa[r].tolist())),
                 **dict(zip(ub[r].tolist(), sb[r].tolist()))}
        check(all(score[u] - kth <= atol for u in a ^ b),
              f"row {r}: top-k uid sets differ beyond ties: {a ^ b}")
        ties += 1
    return max_diff, ties


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def steady_s(fn, reps: int = 5) -> float:
    fn()
    times = []
    for _ in range(reps):
        _, dt = timed(fn)
        times.append(dt)
    return float(np.median(times))


def background_rows(seed: int, n: int, dim: int, norm: float) -> np.ndarray:
    x = np.random.default_rng(seed).standard_normal((n, dim))
    x *= norm / np.linalg.norm(x, axis=1, keepdims=True)
    return x.astype(np.float32)


def run_one_chip(spec, devices, *, seed: int, bank_rows: int = BANK_ROWS,
                 n_items: int = N_ITEMS) -> dict:
    cfg = spec.model
    E = cfg.embed_dim
    n_queries = spec.shape("query_batch").global_batch

    (engine, query, _), setup_s = timed(lambda: build_service(
        spec, seed=seed, search_devices=devices))
    store, bank = engine.store, engine.store.device_bank
    print(f"set-up (weights, predictor, engines): {setup_s:.2f} s")
    print(f"scan: impl={bank.impl} interpret={bank.interpret} "
          f"shards={bank.n_shards} block_n={bank.block_n}")
    check(bank.impl == "pallas" and bank.interpret is False,
          f"the scan is {bank.impl} interpret={bank.interpret}, not the "
          f"compiled kernel")

    n_bg = bank_rows - n_items
    full = cfg.tower("vision").n_layers
    bg = background_rows(seed, n_bg, E, BG_NORM)
    _, add_s = timed(lambda: store.add_batch(
        np.arange(10**9, 10**9 + n_bg), bg, np.zeros(n_bg),
        np.full(n_bg, full), fine=True))
    print(f"background rows: {n_bg} via add_batch in {add_s:.2f} s")

    data = SYN.multimodal_pairs(seed + 1, max(n_items, n_queries), cfg)
    engine.submit_batch(np.arange(n_items), data.items["vision"][:n_items])
    stats, ingest_s = timed(engine.drain)
    print(f"ingest: {stats.n_embedded} items in {ingest_s:.2f} s (cold), "
          f"avg exit layer {stats.avg_layers:.1f}/{full}, "
          f"{stats.group_batches} exit groups")
    check(len(store) == bank_rows, f"store holds {len(store)} rows")

    texts = data.items["text"][:n_queries]
    results, cold_s = timed(lambda: query.query_batch(texts, k=K))
    n_refined = sum(r.n_refined for r in results)
    print(f"query_batch: {n_queries} queries in {cold_s:.2f} s (cold), "
          f"{n_refined} candidates refined")
    n_cont = sum(f._cache_size() for f in engine._jit_continue.values())
    print(f"continuation executables compiled so far: {n_cont} (one per "
          f"exit span and group size)")
    check(n_refined > 0, "round 3 refined no candidate")
    check(all(len(r.uids) > 0 for r in results), "a query returned nothing")
    check(len(bank) == bank_rows, f"bank holds {len(bank)} rows")
    print(f"bank rows: {len(bank)} (capacity {bank.capacity})")

    QG = query.embed_query_batch(texts).reshape(-1, E)    # round-1 queries
    # directions of background rows drawn across the bank: their winners
    # sit in every block, so the running merge must keep late replacements
    pick = np.random.default_rng(seed + 2).choice(n_bg, len(QG),
                                                  replace=False)
    bg_q = bg[pick] / BG_NORM
    for name, qs in (("round-1 queries", QG), ("background queries", bg_q)):
        du, ds = store.search_batch(qs, K, impl=query.search_impl)
        nu, ns = store.search_batch(qs, K, impl="numpy")
        diff, ties = topk_agree(du, ds, nu, ns, SCORE_ATOL)
        blocks = np.unique(store.rows_of(du.ravel()) // bank.block_n)
        print(f"scan parity, {name} (Q={len(qs)}): device == numpy, max "
              f"|score diff| {diff:.3g} <= {SCORE_ATOL}, {ties} rows differ "
              f"by ties; winners in {blocks.size} of "
              f"{-(-bank.capacity // bank.block_n)} scan blocks")

    scan_s = steady_s(lambda: store.search_batch(QG, K,
                                                 impl=query.search_impl))
    _, warm_s = timed(lambda: query.query_batch(texts, k=K))
    print(f"steady: round-1 scan {scan_s * 1e3:.2f} ms (median of 5); "
          f"query_batch again {warm_s:.2f} s")

    emb = query.embed_query_batch(texts)                   # (B, G, E) bf16
    tp32 = jax.tree.map(lambda a: a.astype(jnp.float32),
                        query.params["towers"]["text"])
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, x: IB.mem_embed_all_exits(
            {"towers": {"text": p}}, cfg32, query.recall, "text",
            x)["exit_embs"])(tp32, jnp.asarray(texts))
    ref = np.asarray(ref)[query._g_rows].transpose(1, 0, 2)
    cos = (emb * ref).sum(-1) / (np.linalg.norm(emb, axis=-1) *
                                 np.linalg.norm(ref, axis=-1))
    for g, layer in enumerate(query.granularities):
        print(f"tower parity, text exit {layer}: min cosine bf16 vs f32 "
              f"{cos[:, g].min():.5f} (>= {COS_MIN})")
    check(cos.min() >= COS_MIN, f"tower cosine {cos.min()} < {COS_MIN}")
    return {"bank": bank}


def run_four_chips(devices, *, seed: int, n: int = BANK_ROWS) -> None:
    check(len(devices) >= 4, f"--four-chips needs 4 devices, "
                             f"found {len(devices)}")
    E = get_arch(ARCH).model.embed_dim
    n_q = get_arch(ARCH).shape("query_batch").global_batch * 3
    rng = np.random.default_rng(seed)
    embs, centers = SYN.clustered_sphere(rng, n, int(np.sqrt(n)), E)
    qs, _ = SYN.clustered_sphere(rng, n_q, centers=centers)
    store = EmbeddingStore(E)
    store.attach_ivf(n_clusters=int(np.sqrt(n)), nprobe=8, min_rows=1)
    for i in range(0, n, 8192):
        store.add_batch(np.arange(i, i + 8192), embs[i:i + 8192],
                        np.zeros(8192), np.ones(8192))
    for _ in range(32):
        if not store.ivf_maybe_recluster():
            break
    print(f"store: {len(store)} rows, IVF {store.ivf_index.n_clusters} "
          f"clusters, nprobe 8, Q={n_q}")

    out = {}
    for n_chips in (1, 4):
        bank = store.attach_device_bank(devices[:n_chips])
        check(bank.impl == "pallas" and bank.interpret is False,
              f"the {n_chips}-chip scan is {bank.impl} interpret="
              f"{bank.interpret}, not the compiled kernel")
        for impl in ("device", "ivf"):
            f0 = store.ivf_fallbacks
            (u, s), cold = timed(lambda: store.search_batch(qs, K, impl=impl))
            warm = steady_s(lambda: store.search_batch(qs, K, impl=impl))
            check(store.ivf_fallbacks == f0, f"{impl} scan fell back")
            out[n_chips, impl] = (u, s)
            print(f"{n_chips} chip(s), {impl}: shards={bank.n_shards}, "
                  f"first call {cold:.2f} s, steady {warm * 1e3:.2f} ms")
    nu, ns = store.search_batch(qs, K, impl="numpy")
    diff, ties = topk_agree(*out[1, "device"], nu, ns, SCORE_ATOL)
    print(f"exhaustive, 1 chip vs numpy: max |score diff| {diff:.3g}, "
          f"{ties} tie rows")
    for impl in ("device", "ivf"):
        diff, ties = topk_agree(*out[4, impl], *out[1, impl], SHARD_ATOL)
        print(f"{impl}, 4 chips vs 1 chip: max |score diff| {diff:.3g} <= "
              f"{SHARD_ATOL}, {ties} tie rows")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the bank sharded over 4 chips, against "
                         "the same store on one chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    cache_dir = enable_compile_cache()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    print(f"device: {dev.device_kind} x{len(devices)} ({dev.platform}); "
          f"compile cache {cache_dir}")
    print("timings below are smoke timings of one run, not benchmark "
          "numbers")
    t0 = time.perf_counter()
    if args.four_chips:
        run_four_chips(devices, seed=args.seed)
    else:
        run_one_chip(get_arch(ARCH), devices[:1], seed=args.seed)
    print(f"total {time.perf_counter() - t0:.2f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
