#!/usr/bin/env python3
"""Run one cell several times, one process after another, and summarise.

    python benchmarks/chip/repeat.py --workload <name> --seeds 1,2,3 \
        --seconds <s> [--trace 0|1] [--out chiprun_out/<dir>]

Each run is ``run.py`` in a process of its own (the chip belongs to one
process at a time); its stdout and stderr go to ``<out>/<seed>.out`` and
``.err``. One line per run is printed as it ends (seed, exit code, wall
seconds, correct, metrics, checks), then, per metric, the median and the
spread: the distance between the quartiles of ``statistics.quantiles``
as a share of the median.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent


def spread(values):
    if len(values) < 2:
        return None
    q1, med, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--out", default="chiprun_out/repeat")
    ap.add_argument("--timeout", type=float, default=1200,
                    help="seconds a run may take before it is stopped")
    args = ap.parse_args()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    runs = []
    for seed in args.seeds.split(","):
        cmd = [sys.executable, str(HERE / "run.py"), "--workload",
               args.workload, "--seed", seed, "--seconds", args.seconds,
               "--trace", args.trace]
        t0 = time.perf_counter()
        try:
            p = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=args.timeout)
        except subprocess.TimeoutExpired as e:
            p = subprocess.CompletedProcess(
                cmd, 124, (e.stdout or b"").decode(errors="replace")
                if isinstance(e.stdout, bytes) else (e.stdout or ""),
                (e.stderr or b"").decode(errors="replace")
                if isinstance(e.stderr, bytes) else (e.stderr or ""))
        wall = time.perf_counter() - t0
        (out / f"{seed}.out").write_text(p.stdout)
        (out / f"{seed}.err").write_text(p.stderr)
        res = None
        if p.returncode == 0 and p.stdout.strip():
            res = json.loads(p.stdout.strip().splitlines()[-1])
        runs.append(res)
        line = {"seed": seed, "rc": p.returncode, "wall_s": round(wall, 1)}
        if res:
            line.update(correct=res["correct"], failed=res["failed"],
                        attempted=res["attempted"],
                        metrics={k: v["value"] for k, v in
                                 res["metrics"].items()},
                        checks=res["checks"],
                        mem=res["device"]["memory_peak_bytes"])
            if "busy_s" in res["device"]:
                line.update(busy_s=res["device"]["busy_s"],
                            window_s=res["device"]["window_s"])
        else:
            line["stderr_tail"] = p.stderr[-3000:]
        print(json.dumps(line), flush=True)
    names = sorted({k for r in runs if r for k in r["metrics"]})
    for k in names:
        vals = [r["metrics"][k]["value"] for r in runs
                if r and k in r["metrics"]]
        print(json.dumps({"metric": k, "n": len(vals),
                          "median": statistics.median(vals),
                          "spread": spread(vals), "values": vals}))
    return 0 if all(runs) else 1


if __name__ == "__main__":
    sys.exit(main())
