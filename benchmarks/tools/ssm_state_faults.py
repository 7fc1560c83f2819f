#!/usr/bin/env python3
"""Does ``correct`` hold the state-space STATE?  Faults of the per-slot
state planted in the program, one at a time, in one process on the chip,
each read through the harness's own comparison:

    python3 benchmarks/tools/ssm_state_faults.py --workload <cell> \
        [--faults none,no_reset,frozen_half,neighbour] [--seed N]

* ``none``: the program as it is, for scale;
* ``no_reset``: the reset at admission is skipped, so a sequence starts
  from what its slot's last tenant left (`conv_state` and `ssm_state`);
* ``frozen_half``: every odd slot's `ssm_state` is written back as it
  was read, whatever the step fed;
* ``neighbour``: every step reads slot i's `ssm_state` from slot i - 1.

Each fault serves the cell's traffic for a warm stretch and one window
as `tools/seed_sweep.py` does (`drivers/serve.sweep`, one seed) and
prints the regret of what it served beside the configuration's limit:
one JSON line a fault to stdout and to
``chiprun_out/sweeps/<cell>.ssm_state_faults.jsonl``.  A fault that
reads UNDER the limit is one the comparison cannot see.  Decides
nothing; the program is patched in this process only.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import run as harness  # noqa: E402  (benchmarks/run.py)


@contextlib.contextmanager
def planted(fault: str):
    """The program with one fault, for the programs traced inside."""
    import jax.numpy as jnp

    from flexflow_tpu.ops import mamba2
    from flexflow_tpu.serving.scheduler import PagedKVDecodeModel

    sound = mamba2.ssd_chunk

    def frozen_half(S0, *fed):
        S1, y = sound(S0, *fed)
        odd = (jnp.arange(S0.shape[0]) % 2 == 1)[:, None, None, None]
        return jnp.where(odd, S0, S1), y

    def neighbour(S0, *fed):
        return sound(jnp.roll(S0, 1, axis=0), *fed)

    if fault == "none":
        yield
        return
    if fault == "no_reset":
        owner, name, wrong = (PagedKVDecodeModel, "reset_slot_state",
                              lambda self, slot: None)
    else:
        owner, name, wrong = mamba2, "ssd_chunk", {
            "frozen_half": frozen_half, "neighbour": neighbour}[fault]
    kept = getattr(owner, name)
    setattr(owner, name, wrong)
    try:
        yield
    finally:
        setattr(owner, name, kept)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--faults",
                    default="none,no_reset,frozen_half,neighbour")
    ap.add_argument("--seed", type=int, default=2_400_000_011)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--benchmark",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()
    ctx, driver = harness.make_context(
        ["--workload", args.workload, "--seed", str(args.seed),
         "--seconds", str(args.seconds), "--benchmark", args.benchmark]
        + (["--rehearse-cpu"] if args.rehearse_cpu else []))
    limit = ctx.cfg["tolerance"]["regret.mean"]["limit"]
    out_dir = os.path.join(ROOT, "chiprun_out", "sweeps")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(
            out_dir, args.workload + ".ssm_state_faults.jsonl"), "w") as f:
        for fault in args.faults.split(","):
            with planted(fault):
                (row,) = driver.sweep(ctx, [args.seed], set())
            read = row["program"].get("regret.mean")
            line = json.dumps({
                "fault": fault, "seed": args.seed, "failed": row["failed"],
                **row["program"], "limit": limit,
                "seen": read is None or read > limit})
            print(line, flush=True)
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
