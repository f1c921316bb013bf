"""Benchmark orchestrator: one harness per paper table/figure.
``python -m benchmarks.run [--only table2_throughput,...]``."""
from __future__ import annotations

import argparse
import importlib
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

SUITES = [
    ("table2_throughput", "Table 2: throughput x accuracy x device"),
    ("fig8_preexit", "Fig 8: pre-exit predictor"),
    ("fig10_plora_step", "Fig 10: P-LoRA step schedule"),
    ("fig11_granularity", "Fig 11: accuracy vs granularity"),
    ("fig13_tradeoff", "Fig 13: throughput-accuracy frontier"),
    ("fig14_ablation", "Fig 14: component ablation"),
    ("fig15_latency", "Fig 15: query latency budget"),
    ("fig16_energy", "Fig 16: energy & memory"),
    ("storage_cost", "§5.4: storage cost"),
    ("store_scale", "Store scaling: insert throughput & query latency"),
    ("check_regression", "Guard: store-scale throughput vs committed baseline"),
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated suite names")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    t0 = time.time()
    failures = []
    for mod_name, desc in SUITES:
        if only and mod_name not in only:
            continue
        print(f"\n{'='*72}\n{desc}  [{mod_name}]\n{'='*72}")
        t1 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{mod_name}")
            mod.main()
            print(f"[{mod_name}] done in {time.time()-t1:.0f}s")
        except Exception:
            traceback.print_exc()
            failures.append(mod_name)
    print(f"\n{'='*72}\nbenchmarks finished in {time.time()-t0:.0f}s; "
          f"{len(failures)} failures{': ' + ', '.join(failures) if failures else ''}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
