"""A tiny copy of the benchmark for CPU tests: the same harness and loops,
smoke widths, a small bank, and cells named ``tiny.*``.

``make_tree(root)`` writes ``root/BENCHMARK.json`` and
``root/benchmarks/chip/{configs,traffic,limits}`` with the real loops,
reference blocks, metric readers and peaks copied in, so a test can also
add files of its own there (a dummy loop, block, cell or metric) without
touching the real ones.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from chipbench import spec

TINY = {
    "name": "tiny", "source": "smoke widths for CPU tests",
    "model": {"embed_dim": 256, "dtype": "bfloat16", "norm_eps": 1e-6,
              "logit_scale_init": 14.285, "towers": [
                  {"modality": "vision", "n_layers": 12, "d_model": 64,
                   "n_heads": 4, "d_ff": 128, "n_tokens": 16, "d_input": 32,
                   "vocab": 0},
                  {"modality": "text", "n_layers": 8, "d_model": 256,
                   "n_heads": 4, "d_ff": 256, "n_tokens": 8, "d_input": 0,
                   "vocab": 100}]},
    "recall": {"exit_interval": 4, "superficial_layers": 7,
               "predictor_hidden": 16, "query_granularities": 3,
               "filter_top_k": 10, "cache_bits": 4},
    "query_batch": 8, "n_candidates": 4096, "reduced": [], "assumed": {},
    "departures": []}

TRAFFIC = {
    "ingest": {"loop": "ingest", "items_per_drain": 32, "item_pool": 64,
               "exit_layer": 8, "kept_per_chunk": 2, "warmup_drains": 2,
               "check_items": 8},
    "fresh": {"loop": "query", "batch": 8, "k": 10, "bank_rows": 16384,
              "filler_norm": 0.05, "placed_fine": 9, "placed_fresh": 1,
              "own_score": 8.0, "pool_drains": 40, "warmup_drains": 2,
              "filler_check_rows": 256},
    "warm": {"loop": "query", "batch": 8, "k": 10, "bank_rows": 16384,
             "filler_norm": 1.0, "placed_fine": 0, "placed_fresh": 0,
             "warmup_drains": 2, "filler_check_rows": 256},
}

# the real cell each tiny cell stands for
REAL = {"ingest": "imagebind.ingest", "fresh": "imagebind.recall-fresh",
        "warm": "clip.recall-warm"}

CPU_PEAKS = {"bf16_flops": 1e12, "int8_ops": 1e12, "hbm_bytes_per_s": 1e11,
             "hbm_bytes": 1e10}


def make_tree(root: Path) -> Path:
    """Write the tiny benchmark under ``root``; returns its bench dir."""
    real = json.loads((spec.CHECKOUT / "BENCHMARK.json").read_text())
    bench = root / "benchmarks" / "chip"
    for sub in ("configs", "traffic", "limits"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    for sub in ("loops", "reference", "end_to_end", "metrics"):
        shutil.copytree(spec.BENCH_DIR / sub, bench / sub, dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(spec.BENCH_DIR / "peaks.json", bench / "peaks.json")
    (bench / "configs" / "tiny.json").write_text(json.dumps(TINY))
    cells = []
    for mix, tr in TRAFFIC.items():
        (bench / "traffic" / f"tiny-{mix}.json").write_text(json.dumps(tr))
        name = f"tiny.{mix}"
        # the tiny cells are held to the real cells' limits
        shutil.copy(spec.BENCH_DIR / "limits" / f"{REAL[mix]}.json",
                    bench / "limits" / f"{name}.json")
        cells.append({"name": name, "config": "tiny",
                      "traffic": f"tiny-{mix}", "chips": 1, "why": "test"})
    tiny = {real_name: f"tiny.{mix}" for mix, real_name in REAL.items()}
    real["configs"] = [{"name": "tiny", "source": "test",
                        "file": "benchmarks/chip/configs/tiny.json",
                        "reduced": [], "why": "test"}]
    real["workloads"] = cells
    for m in real["end_to_end"] + real["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [tiny[w] for w in m["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(real, indent=1))
    return bench
