#!/usr/bin/env python3
"""Find a serving cell's knee once, by one sweep on the chip:

    python3 benchmarks/tools/knee_sweep.py --workload <cell> \
        --rates 4,8,12,16,20,24 --seconds 20

builds the server once and offers the cell's traffic at each rate in
turn, lowest first (the warm stretch and one window each, drained in
between).  The knee is the highest rate at which the queue does not
grow through the window (``queue_depth_at_end`` no deeper than
``queue_depth_at_t0`` and than the slots) and tokens/s still rise with
the rate.  The number goes into the traffic files as ``rate_rps``
(0.8 x and 1.25 x knee); PERF.md keeps the readings.
"""
from __future__ import annotations

import argparse
import json
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=2_400_000_011)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--benchmark",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()
    ctx, driver = harness.make_context(
        ["--workload", args.workload, "--seed", str(args.seed),
         "--seconds", str(args.seconds), "--benchmark", args.benchmark]
        + (["--rehearse-cpu"] if args.rehearse_cpu else []))
    ff = ctx.family.build_server(ctx.cfg, ctx.devices)
    front = driver.open_front(ctx, ff, args.seed)
    out_dir = os.path.join(ROOT, "chiprun_out", "sweeps")
    os.makedirs(out_dir, exist_ok=True)
    try:
        with open(os.path.join(out_dir, args.workload + ".knee.jsonl"),
                  "w") as f:
            for i, rate in enumerate(float(r) for r in args.rates.split(",")):
                s, _, _ = driver.one_window(
                    ctx, front, args.seed + i, args.seconds, rate)
                s["rate_rps"] = rate
                line = json.dumps(s)
                print(line, flush=True)
                f.write(line + "\n")
    finally:
        front.close(30.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
