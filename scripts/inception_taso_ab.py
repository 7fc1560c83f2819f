#!/usr/bin/env python
"""InceptionV3 joint-search A/B: is a rewrite (TASO catalog or built-in
merge) load-bearing on the real chip?  (VERDICT r4 #1; reference AE
/root/reference/scripts/osdi22ae/inception.sh — Unity vs DP on
Inception b=64 budget=10.)

Two searches over the identical model, measured back-to-back on chip:
  A "no-rewrites": rewrite enumeration disabled (max_variants=1),
    catalog off — parallelization-only search;
  B "joint": TASO catalog default-on + built-ins, rewrite_depth=3,
    rewrite_max_variants=16 — the full joint rewrite+parallelization
    search.

Prints one JSON line with both step times, the winning trace, and the
delta.  Honest either way: a ~0 delta with the trace shown is evidence
of the single-chip ceiling, not a failure to run.

Usage: python scripts/inception_taso_ab.py [--batch 32] [--px 299]
       [--iters 12] [--windows 3] [--cpu-smoke]
"""
import argparse
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_HERE, ".."))
sys.path.insert(0, _HERE)

from _ab_common import interleaved_best, make_train_window, summarize  # noqa: E402


def build(cfg_kwargs, batch, px, classes, dev):
    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models import build_inception_v3

    cfg = FFConfig(**cfg_kwargs)
    ff = FFModel(cfg)
    build_inception_v3(ff, batch_size=batch, num_classes=classes,
                       image_size=px)
    t0 = time.perf_counter()
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=[dev])
    search_s = time.perf_counter() - t0
    return ff, search_s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--px", type=int, default=299)
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--iters", type=int, default=12)
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--budget", type=int, default=10)
    ap.add_argument("--cpu-smoke", action="store_true",
                    help="tiny config on the host CPU (logic check)")
    args = ap.parse_args()

    if args.cpu_smoke:
        import jax

        jax.config.update("jax_platforms", "cpu")
        args.batch, args.px, args.iters, args.windows = 4, 75, 2, 1
    import jax

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    dtype = "bfloat16" if on_tpu else "float32"
    common = dict(batch_size=args.batch, num_devices=1,
                  search_budget=args.budget, search_calibrate=False,
                  compute_dtype=dtype)

    import numpy as np

    # build both, then INTERLEAVE timing windows A/B/A/B...: drift is
    # time-correlated, so alternating windows puts both variants under
    # the same conditions (best-of-N per side)
    variants = (
        ("no_rewrites", dict(substitution_json="none",
                             rewrite_max_variants=1)),
        ("joint", dict(rewrite_depth=3, rewrite_max_variants=16)),
    )
    rng = np.random.RandomState(0)
    xs = rng.randn(args.batch, 3, args.px, args.px).astype(np.float32)
    ys = rng.randint(0, args.classes, args.batch).astype(np.int32)
    legs, windows = {}, {}
    for tag, extra in variants:
        print(f"[{tag}] searching + compiling ...", file=sys.stderr)
        ff, search_s = build({**common, **extra}, args.batch, args.px,
                             args.classes, dev)
        legs[tag] = {
            "search_compile_s": round(search_s, 1),
            "rewrites": [list(r) for r in ff.strategy.rewrites],
        }
        windows[tag] = make_train_window(ff, {"input": xs}, ys, args.iters)
    for tag, timing in summarize(
            interleaved_best(windows, args.windows)).items():
        legs[tag].update(timing)
        legs[tag]["samples_per_sec"] = round(
            args.batch / (legs[tag]["step_ms"] / 1e3), 2)

    a, b = legs["no_rewrites"], legs["joint"]
    out = {
        "workload": f"InceptionV3 {args.px}px b{args.batch} {dtype} "
                    f"single-chip, search budget {args.budget}",
        "no_rewrites": a,
        "joint": b,
        "speedup": round(a["step_ms"] / b["step_ms"], 4),
        "winning_rules": sorted({r[0] for r in b["rewrites"]}),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
