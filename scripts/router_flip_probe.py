#!/usr/bin/env python3
"""How many of a cell's routed (token, layer) choices a lower precision
flips: the family's float32 reference against the same reference with
the operands of every matrix product rounded to `--precision`
(bfloat16: what the program computes in), on the seed's weights and
first sequence.  Prints the share of (token, layer) top-k SETS that
differ and the share of pairs; for the configuration's
``tolerance_note`` (a flipped pair moves a whole expert's gradient).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import run as harness  # noqa: E402  (benchmarks/run.py)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+",
                    default=[2_300_000_011])
    ap.add_argument("--precision", default="bfloat16")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--benchmark",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()
    ctx, _ = harness.make_context(
        ["--workload", args.workload, "--seed", str(args.seeds[0]),
         "--seconds", "1", "--benchmark", args.benchmark]
        + (["--rehearse-cpu"] if args.rehearse_cpu else []))
    import jax
    import jax.numpy as jnp
    import numpy as np

    fam, cfg, tr = ctx.family, ctx.cfg, ctx.traffic

    @jax.jit
    def flips(w, ids):
        with jax.default_matmul_precision("highest"):
            _, want = fam.forward(w, ids, cfg, "float32")
            _, got = fam.forward(w, ids, cfg, args.precision)
        sets = jnp.stack([jnp.any(a != b, axis=-1)
                          for a, b in zip(got, want)])
        # both sorted: a pair flipped = an id of one set not in the other
        pairs = jnp.stack([
            jnp.sum(~jnp.any(a[..., :, None] == b[..., None, :], axis=-1),
                    axis=-1) for a, b in zip(got, want)])
        return jnp.mean(sets, axis=1), jnp.mean(pairs, axis=1) / want[0].shape[-1]

    for seed in args.seeds:
        w = fam.make_weights(cfg, seed, "program")
        inputs, _ = fam.make_batch(cfg, 1, tr["seq"],
                                   np.random.default_rng(seed))
        sets, pairs = flips(w, jnp.asarray(inputs["input"][0]))
        print(json.dumps({
            "seed": seed, "precision": args.precision,
            "sets_flipped_by_layer": [round(float(v), 5) for v in sets],
            "pairs_flipped_by_layer": [round(float(v), 5) for v in pairs],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
