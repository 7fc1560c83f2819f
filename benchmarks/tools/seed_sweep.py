#!/usr/bin/env python3
"""Rule 3 of how ``correct`` is decided, in one process on the chip:

    python3 benchmarks/tools/seed_sweep.py --workload <cell> \
        --seeds 12 --control-seeds 3 [--first-seed N]

reads the cell's statistics for a dozen seeds with the program at its
stated precision, and for a few of them with the reference put in the
program's place one precision down (`reference.CONTROL_BELOW`).  One
JSON line per seed goes to stdout and to
``chiprun_out/sweeps/<cell>.jsonl``; the last line gives the largest
sound reading, the smallest control reading and their geometric mean
per statistic.  The limits in the configuration's ``tolerance`` are set
from these by hand (PERF.md records both readings beside each limit).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import run as harness  # noqa: E402  (benchmarks/run.py)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_300_000_011)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--benchmark",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()
    ctx, driver = harness.make_context(
        ["--workload", args.workload, "--seed", str(args.first_seed),
         "--seconds", str(args.seconds), "--benchmark", args.benchmark]
        + (["--rehearse-cpu"] if args.rehearse_cpu else []))
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    out_dir = os.path.join(ROOT, "chiprun_out", "sweeps")
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    with open(os.path.join(out_dir, args.workload + ".jsonl"), "w") as f:
        for row in driver.sweep(ctx, seeds, set(seeds[:args.control_seeds])):
            rows.append(row)
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()
        summary = {}
        for stat in rows[0]["program"]:
            sound = max(r["program"][stat] for r in rows)
            control = min(r["control"][stat] for r in rows if "control" in r)
            summary[stat] = {"sound_max": sound, "control_min": control,
                             "ratio": control / sound if sound else math.inf,
                             "geometric_mean": math.sqrt(sound * control)}
        line = json.dumps({"summary": summary})
        print(line, flush=True)
        f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
