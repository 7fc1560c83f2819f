#!/usr/bin/env python3
"""What a stall of the host does to a training cell's rate, in one
process on the chip:

    python3 benchmarks/tools/stall_probe.py --workload <cell> \
        [--seconds 10] [--depths 4,24] [--stalls 0,0.25,1.0]

brings the cell up once, then runs the driver's own timed window
(`drivers/train.py window`) for every depth of the host's queue
(``in_flight``) and every stall: a `time.sleep` of that many seconds put
after one `train_step` in the middle of the window, which is what a
host whose cores are shared does to the benchmark now and then.  One
JSON line per window goes to stdout and to
``chiprun_out/stall_probe/<cell>.jsonl``.  The traffic file's
``in_flight`` is set from these readings (PERF.md §2).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import run as harness  # noqa: E402  (benchmarks/run.py)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2_400_000_011)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--depths", default="4,24")
    ap.add_argument("--stalls", default="0,0.25,1.0")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--benchmark",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()
    ctx, driver = harness.make_context(
        ["--workload", args.workload, "--seed", str(args.seed),
         "--seconds", str(args.seconds), "--benchmark", args.benchmark]
        + (["--rehearse-cpu"] if args.rehearse_cpu else []))
    import jax

    ff, batch = driver.bring_up(ctx)
    batches = driver.first_step(ctx, ff, ctx.seed, batch)
    for inputs, labels in batches[1:3]:
        jax.block_until_ready(ff.train_step(inputs, labels)["loss"])
    tokens = batch * ctx.traffic["seq"] / len(ctx.devices)

    step, state = ff.train_step, {}

    def stalling_step(inputs, labels):
        out = step(inputs, labels)
        # once, after the first dispatch past the window's middle
        if state["stall"] and time.monotonic() >= state["at"]:
            time.sleep(state["stall"])
            state["stall"] = 0.0
        return out

    ff.train_step = stalling_step
    out_dir = os.path.join(ROOT, "chiprun_out", "stall_probe")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, args.workload + ".jsonl"), "w") as f:
        for depth in map(int, args.depths.split(",")):
            for stall in map(float, args.stalls.split(",")):
                state.update(stall=stall,
                             at=time.monotonic() + args.seconds / 2)
                w = driver.window(ctx, ff, batches, args.seconds, depth)
                w.pop("losses")
                row = {"in_flight": depth, "stall_s": stall,
                       "tokens_per_s_per_chip":
                           w["steps"] * tokens / w["window_s"], **w}
                line = json.dumps(row)
                print(line, flush=True)
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
