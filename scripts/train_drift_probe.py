#!/usr/bin/env python3
"""How a training cell's step time and routed-expert counts move with
the step index: `--steps` steps of the cell's own batches, timed ten at
a time with a wait after each ten, the `train_step.moe` counts beside
them.  One line per `--alpha` (Adam's step size; the configuration's
when none is given).  On the chip: `chiprun -- python3
scripts/train_drift_probe.py --workload <cell> --alpha 1e-4 --alpha 1e-5`.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import run as harness  # noqa: E402  (benchmarks/run.py)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2_300_000_011)
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--alpha", type=float, action="append")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--benchmark",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()
    ctx, driver = harness.make_context(
        ["--workload", args.workload, "--seed", str(args.seed),
         "--seconds", "1", "--benchmark", args.benchmark]
        + (["--rehearse-cpu"] if args.rehearse_cpu else []))
    import jax

    from flexflow_tpu.obs import trace

    for alpha in args.alpha or [ctx.cfg["optimizer"]["alpha"]]:
        ctx.cfg["optimizer"]["alpha"] = alpha
        ff, batch = driver.bring_up(ctx)
        batches = driver.first_step(ctx, ff, args.seed, batch)
        first = [r.args for r in trace.spans()
                 if r.name == "train_step" and r.args.get("first")][-1]
        print(json.dumps({"first_step": first, "memory": {
            k: v for k, v in (jax.devices()[0].memory_stats() or {}).items()
            if k in ("peak_bytes_in_use", "bytes_limit")}}), flush=True)
        for start in range(0, args.steps, 10):
            seen = len(trace.spans())
            t0 = time.monotonic()
            for i in range(start, start + 10):
                m = ff.train_step(*batches[i % len(batches)])
            jax.block_until_ready(m["loss"])
            ms = 1e2 * (time.monotonic() - t0)
            moe = [r.args for r in trace.spans()[seen:]
                   if r.name == "train_step.moe"]
            print(json.dumps({
                "alpha": alpha, "steps": f"{start}-{start + 9}",
                "ms_a_step": round(ms, 2),
                "loss": round(float(m["loss"]), 4),
                **({k: moe[-1][k] for k in
                    ("moe_pairs", "moe_max_rows", "moe_rows_computed",
                     "moe_overflow")} if moe else {})}), flush=True)
        del ff
    return 0


if __name__ == "__main__":
    sys.exit(main())
