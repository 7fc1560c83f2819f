#!/usr/bin/env python3
"""The two controls of the MECHANISM for a cell whose attention reads
selected keys, in one process on the chip, on the reference's side only:

    python3 benchmarks/tools/dsa_controls.py --workload <cell> \
        [--seed N] [--requests 12] [--seconds 30]

serves the cell's traffic for one window as the driver does, then scores
a seeded sample of the completed requests' served tokens THREE times by
the family's float32 reference (`position_regrets(.., selection=)`): as
the equations say (`dsa`: the number `correct` is decided by), with
selection OFF (`dense`: every causal key attended) and with every
`shared` layer reading the picks of the `full` layer ABOVE it
(`above`).  Each over all served positions and over those past
`index_topk` alone.  A program that selects must read far beyond the
cell's limit against both other references; if it does not, the check
cannot see the mechanism.  One JSON line a reference to stdout and to
``chiprun_out/sweeps/<cell>.dsa_controls.jsonl``.  Decides nothing.
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

import numpy as np  # noqa: E402

import run as harness  # noqa: E402  (benchmarks/run.py)

SELECTIONS = ("dsa", "dense", "above")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2_500_000_011)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--benchmark",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()
    ctx, driver = harness.make_context(
        ["--workload", args.workload, "--seed", str(args.seed),
         "--seconds", str(args.seconds), "--benchmark", args.benchmark]
        + (["--rehearse-cpu"] if args.rehearse_cpu else []))
    fam, cfg = ctx.family, ctx.cfg
    front = driver.open_front(ctx, fam.build_server(cfg, ctx.devices),
                              args.seed)
    try:
        _, sent, _ = driver.one_window(ctx, front, args.seed, args.seconds)
    finally:
        front.close(30.0)
    good = [r for r in sent if r["error"] is None and r.get("tokens")]
    pick = np.random.default_rng(args.seed).permutation(
        len(good))[:args.requests]
    w = fam.make_weights(cfg, args.seed, "reference")
    topk = cfg["index_topk"]
    out_dir = os.path.join(ROOT, "chiprun_out", "sweeps")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, args.workload + ".dsa_controls.jsonl"),
              "w") as f:
        for selection in SELECTIONS:
            total = {"all": [0.0, 0], "past_topk": [0.0, 0]}
            for i in pick:
                rec = good[int(i)]
                toks, plen = rec["tokens"], len(rec["prompt"])
                ids = np.zeros(cfg["n_positions"], np.int32)
                ids[:len(toks)] = toks
                regret = np.asarray(fam.position_regrets(
                    w, ids, selection=selection))
                # logits at p judge the token at p + 1
                served = np.arange(plen - 1, len(toks) - 1)
                for name, rows in (("all", served),
                                   ("past_topk", served[served >= topk])):
                    total[name][0] += float(regret[rows].sum())
                    total[name][1] += len(rows)
            row = {"reference": selection, "seed": args.seed,
                   "requests": len(pick),
                   **{f"regret.mean.{k}": s / max(n, 1)
                      for k, (s, n) in total.items()},
                   **{f"positions.{k}": n for k, (_, n) in total.items()}}
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
