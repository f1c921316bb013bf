#!/usr/bin/env python3
"""The control: the plain reference put in the program's place at the
precision below the configuration's, judged by the cell's own limits.

    python benchmarks/chip/control.py --workload <name> --seeds 1,2,3

The configurations state bfloat16, so the control computes every tower
matmul with its operands rounded to float8_e4m3 (per-tensor scale), and the
round-1 scan with its queries rounded to int8 (per-row scale). Its loop
(``loops/<loop>.py``, ``Loop.control``) reads each number a run of the
cell compares (see ``chipbench/gaps.py``) on the inputs a run checks, at
the cell's own sizes. Those readings go through the comparison a run makes
(``chipbench.verdict.judge``, against ``limits/<workload>.json``); numbers
compared exactly (limit 0) have no control reading and are left out. The
control has to come out not correct on every seed: one JSON line per seed
with its verdict and readings (the smallest reading over the seeds is a
number's upper reading), and exit code 1 if any seed came out correct.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import spec, verdict  # noqa: E402


def judge(cell, seed: int) -> dict:
    """The control's verdict for one seed, by the run's own comparison."""
    got = spec.loop_class(cell.traffic["loop"],
                          bench_dir=cell.bench_dir).control(cell, seed)
    numbers = cell.limits["numbers"]
    unread = [k for k, v in numbers.items()
              if v["limit"] != 0 and k not in got]
    if unread:
        raise spec.SpecError(f"the control reads none of {unread}")
    correct, checks = verdict.judge(
        {k: v for k, v in numbers.items() if k in got}, got)
    return {"workload": cell.name, "seed": seed, "cast": "fp8",
            "correct": correct,
            "checks": {k: [v["value"], v["limit"]]
                       for k, v in checks.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    any_correct = False
    for seed in args.seeds.split(","):
        r = judge(cell, int(seed))
        any_correct |= r["correct"]
        for k, (v, lim) in r["checks"].items():
            print(f"control {args.workload} seed {seed} {k}: {v!r} "
                  f"{'<=' if v <= lim else '>'} {lim!r}", file=sys.stderr)
        print(json.dumps(r), flush=True)
    return 1 if any_correct else 0


if __name__ == "__main__":
    sys.exit(main())
