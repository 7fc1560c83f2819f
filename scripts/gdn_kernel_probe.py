#!/usr/bin/env python
"""The delta rule's recurrence alone at the serving cell's widths (PR 35).

Run by hand on the chip; no switch in the program reads anything here.

`[64, 32, 128, 128]` float32 state (qwen3-next-ep4-serve: 64 slots, 32
value heads of 128 x 128), one layer:

  * a decode step (s = 1, every row live) and a prefill chunk (s = 8,
    29 of 64 rows live with counts 1..8, the rest riders);
  * the plain recurrence (`delta_rule_scan`) against
    the Pallas kernel (`ops/pallas/gated_delta_rule.py`) at each
    `--heads-block`: largest difference of `S` and of the live rows'
    `o`, whether the skipped rows' state is the input's to the byte,
    and milliseconds a call.

Timing: `--iters` calls chained in one jitted `lax.fori_loop` with real
dataflow (the state is the carry; `o` feeds the next call's `v` through
`0 * sum`), scripts/flash_ceiling_probe.py's discipline.  The floor by
bytes is the live rows' state read and written once at 819 GB/s.
Prints one JSON line a case and writes them under
chiprun_out/gdn_probe/.
"""
import argparse
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.join(_HERE, "..")
sys.path.insert(0, _ROOT)
OUT = os.path.join(_ROOT, "chiprun_out", "gdn_probe")

SLOTS, HEADS, DK, DV = 64, 32, 128, 128
HBM_BYTES_PER_S = 819e9


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--heads-block", default="0,8,16,32",
                    help="0 = what heads_per_block picks")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops.gated_delta_net import delta_rule_scan
    from flexflow_tpu.ops.pallas import gated_delta_rule as gdr

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")

    def inputs(s, counts):
        r = np.random.default_rng(args.seed + s)
        b, h = SLOTS, HEADS
        k = r.normal(size=(b, s, h, DK)).astype(np.float32)
        k /= np.linalg.norm(k, axis=-1, keepdims=True)
        q = r.normal(size=(b, s, h, DK)).astype(np.float32)
        q /= np.linalg.norm(q, axis=-1, keepdims=True) * DK ** 0.5
        real = (np.arange(s)[None, :] < counts[:, None])[..., None]
        g = np.where(real, -r.uniform(0, 2, (b, s, h)), 0.0)
        beta = np.where(real, r.uniform(0, 1, (b, s, h)), 0.0)
        arrs = (r.normal(size=(b, h, DK, DV)), q, k,
                r.normal(size=(b, s, h, DV)), g, beta)
        return ([jnp.asarray(a, jnp.float32) for a in arrs]
                + [jnp.asarray(counts, jnp.int32)])

    def plain(S, q, k, v, g, beta, count):
        return delta_rule_scan(S, q, k, v, g, beta)

    def chained(fn):
        def run(S, q, k, v, g, beta, count):
            def body(_, carry):
                S, v, acc = carry
                S, o = fn(S, q, k, v, g, beta, count)
                return S, v + 0.0 * jnp.sum(o), acc + jnp.sum(o)
            return jax.lax.fori_loop(0, args.iters, body,
                                     (S, v, jnp.float32(0)))
        return jax.jit(run)

    def timed(fn, xs):
        f = chained(fn)
        jax.block_until_ready(f(*xs))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*xs))
            best = min(best, time.perf_counter() - t0)
        return best / args.iters * 1e3

    r = np.random.default_rng(1)
    riders = np.zeros(SLOTS, np.int64)
    live = r.choice(SLOTS, 29, replace=False)
    riders[live] = r.integers(1, 9, 29)
    riders[live[:20]] = 8
    cases = (("decode_all_live", 1, np.ones(SLOTS, np.int64)),
             ("decode_48_live", 1, (np.arange(SLOTS) % 4 > 0).astype(int)),
             ("prefill_29_live", 8, riders))
    os.makedirs(OUT, exist_ok=True)
    lines = []
    for name, s, counts in cases:
        xs = inputs(s, counts)
        S0 = np.asarray(xs[0])
        Sp, op = map(np.asarray, jax.jit(plain)(*xs))
        alive = counts > 0
        row_bytes = HEADS * DK * DV * 4
        line = {"case": name, "s": s, "rows_live": int(alive.sum()),
                "plain_ms": timed(plain, xs),
                "floor_ms": 2 * int(alive.sum()) * row_bytes
                / HBM_BYTES_PER_S * 1e3,
                "device": {"platform": dev.platform,
                           "kind": dev.device_kind}}
        for hb in (int(x) for x in args.heads_block.split(",")):
            if hb and (HEADS % hb or hb * s > 128):
                continue
            fn = (lambda *a, hb=hb: gdr.gated_delta_rule(
                *a, heads_block=hb or None))
            Sk, ok = map(np.asarray, jax.jit(fn)(*xs))
            key = f"hb{hb or gdr.heads_per_block(HEADS, s)}" + (
                "" if hb else "_picked")
            line[key] = {
                "ms": timed(fn, xs),
                "S_err": float(np.abs(Sk - Sp)[alive].max()
                               / np.abs(Sp).max()),
                "o_err": float(np.abs(ok - op)[alive].max()
                               / np.abs(op).max()),
                "skipped_rows_equal_input": bool(
                    np.array_equal(Sk[~alive], S0[~alive])),
                "skipped_o_zero": not bool(ok[~alive].any()),
            }
        print(json.dumps(line), flush=True)
        lines.append(line)
    with open(os.path.join(OUT, "probe.json"), "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
