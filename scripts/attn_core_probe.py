#!/usr/bin/env python
"""The attention core of a seq-512 training step alone (PR 33).

Run by hand on the chip; no switch in the program reads anything here.

  --mode sweep
      The attention core at b 8, h 16, d 64, forward + backward,
      kv 128..1024: the dense path as `_attend` writes it against
      whatever `flash_mha` picks for that length (and the
      long-row kernels through `mha_flash` beside it).

Where the core's time goes INSIDE the real step is no longer this
script's to sum (its `--mode step` summed the xplane's events by shape,
PR 33): a traced run of cell 1, or `python -m benchmarks.device_scopes`
on any capture, prints it under `MultiHeadAttention | core` (PR 38).

Timing of the sweep: `iters` calls chained in one jitted lax.scan with
real dataflow (scripts/flash_ceiling_probe.py's discipline).  Prints one
JSON line and writes it under chiprun_out/attn_probe/.
"""
import argparse
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.join(_HERE, "..")
sys.path.insert(0, _ROOT)
OUT = os.path.join(_ROOT, "chiprun_out", "attn_probe")


def mode_sweep(args) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from flexflow_tpu.ops.pallas import flash_attention as fa

    b, h, d = args.batch, args.heads, args.head_dim
    scale = 1.0 / np.sqrt(d)
    if args.tile_elems:  # the probe's own knob: f32 score elements a cell
        fa._ONE_TILE_ELEMS = args.tile_elems

    def dense(q, k, v):  # ops/attention.py `_attend`'s dense branch
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    def long_rows(q, k, v):
        return fa.mha_flash(q, k, v, scale, False)

    paths = {"dense": dense, "long_row_kernels": long_rows}
    picked = getattr(fa, "flash_mha", None)
    if picked is not None:
        paths["picked"] = lambda q, k, v: picked(q, k, v, scale, False)

    def timed(fn, q, k, v, backward=True):
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

        grads = jax.grad(loss, argnums=(0, 1, 2))

        def body(c, _):
            if not backward:
                return c + 0.0 * fn(c, k, v).astype(c.dtype), None
            dq, dk, dv = grads(c, k, v)
            return c + 0.0 * (dq + dk + dv).astype(c.dtype), None

        f = jax.jit(lambda c: lax.scan(body, c, None, length=args.iters)[0])
        jax.block_until_ready(f(q))
        best = float("inf")
        for _ in range(args.windows):
            t0 = time.perf_counter()
            jax.block_until_ready(f(q))
            best = min(best, (time.perf_counter() - t0) / args.iters)
        return best

    rng = np.random.RandomState(0)
    rows = {}
    for s in (int(x) for x in args.seqs.split(",")):
        q, k, v = (jnp.asarray(rng.randn(b, s, h, d) * 0.5, jnp.bfloat16)
                   for _ in range(3))
        row = {}
        if picked is not None:
            row["tile"] = fa.pick_tiling(s, d)
        for name, fn in paths.items():
            if name == "long_row_kernels" and not fa._supported(
                    q.reshape(b * h, s, d), k.reshape(b * h, s, d)):
                continue
            try:
                row[name + "_ms"] = round(1e3 * timed(fn, q, k, v), 4)
                row[name + "_fwd_ms"] = round(
                    1e3 * timed(fn, q, k, v, backward=False), 4)
            except Exception as e:  # a refusal is a reading too
                row[name + "_error"] = f"{type(e).__name__}: {e}"[:300]
        # 5 score-sized products (S, PV, dV, dP, dQ, dK less the one
        # recompute) of 2*b*h*s*s*d each: what the mathematics needs
        row["ms_at_peak_6_products"] = round(
            6 * 2.0 * b * h * s * s * d / 197e12 * 1e3, 4)
        rows[str(s)] = row
        print(f"kv {s}: {row}", file=sys.stderr)
    return {"b": b, "h": h, "d": d, "fwd_bwd": rows}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("sweep",), default="sweep")
    ap.add_argument("--tag", default="sweep")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--seed", type=int, default=3300000001)
    ap.add_argument("--seqs", default="128,256,512,1024")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--tile-elems", type=int, default=0)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    out = {"probe": args.tag, "platform": dev.platform,
           "device_kind": dev.device_kind,
           **mode_sweep(args)}
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, f"{args.tag}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
