#!/usr/bin/env python3
"""Choose a serving cell's trace (`shape_seed`) so that no completion
falls near an end of the window:

    python3 benchmarks/tools/window_probe.py --workload <cell> \
        --shape-seeds 2905,2906,2907

`serve_tokens_per_s` counts the requests SEEN TO COMPLETE inside the
window, so it moves in steps of one completion (4 % in a window of
some forty): with a completion 0.03 s from the window's end, one run
in four counts it on the other side.  One process, one server; for
each candidate the warm stretch and one window of the cell's own mix at
its own rate, drained in between.  Prints, per candidate, the window's
summary with `completion_nearest_an_end_s`: take a trace whose nearest
completion lies half a second or more from either end, then confirm it
with whole runs of the cell.
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
    ap.add_argument("--shape-seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=2_400_000_029)
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
    try:
        for i, shape_seed in enumerate(
                int(s) for s in args.shape_seeds.split(",")):
            ctx.traffic["shape_seed"] = shape_seed
            s, _, _ = driver.one_window(ctx, front, args.seed + i,
                                        args.seconds)
            print(json.dumps({"shape_seed": shape_seed, **s}), flush=True)
    finally:
        front.close(30.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
