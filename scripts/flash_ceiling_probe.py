#!/usr/bin/env python
"""Flash-attention ceiling campaign kit (VERDICT r4 #5).

Per-kernel timing for the Pallas flash kernels (fwd, and the bwd pair
with independent dq/dkv tiles) plus two calibration probes: a large
plain matmul (the chip's practical MXU rate through this harness) and
XLA's unfused attention at the same shape (the do-nothing alternative).

Timing discipline: `iters` kernel invocations are CHAINED inside one
jitted lax.scan with real dataflow (carry + 0.0*result — floats are
never constant-folded), so one device program runs the whole window and
the per-call dispatch appears once, not per iteration.  The run-to-run
spread on the current machine is not measured: repeat orderings before
trusting a single cell (the r5 sweep ran every cell 2-3x across
sessions before picking _PREFERRED).

Prints one JSON line; run on the bench chip.

Usage: python scripts/flash_ceiling_probe.py [--bh 96] [--d 64]
       [--seqs 2048,4096,8192] [--iters 15] [--windows 3] [--causal]
"""
import argparse
import functools
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_HERE, ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bh", type=int, default=96)  # bench leg: b8 x 12 heads
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--seqs", type=str, default="2048,4096,8192")
    ap.add_argument("--iters", type=int, default=15)
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--causal", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from flexflow_tpu.ops.pallas import flash_attention as fa
    from flexflow_tpu.sim.machine_model import detect_device_spec

    spec = detect_device_spec()
    peak, hbm = spec.peak_flops, spec.hbm_bandwidth
    scale = 1.0 / np.sqrt(args.d)
    causal = args.causal

    def timed(fn, carrier):
        def body(c, _):
            r = fn(c)
            return c + 0.0 * r.astype(c.dtype), None

        f = jax.jit(lambda c: lax.scan(body, c, None,
                                       length=args.iters)[0])
        jax.block_until_ready(f(carrier))
        best = float("inf")
        for _ in range(args.windows):
            t0 = time.perf_counter()
            jax.block_until_ready(f(carrier))
            best = min(best, (time.perf_counter() - t0) / args.iters)
        return best

    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(8192, 8192), jnp.bfloat16)
    b = jnp.asarray(rng.randn(8192, 8192), jnp.bfloat16)
    dt = timed(lambda c: c @ b, a)
    matmul_tfs = 2 * 8192**3 / dt / 1e12
    print(f"calibration matmul 8192^3: {dt*1e3:.3f} ms "
          f"-> {matmul_tfs:.1f} TF/s", file=sys.stderr)

    results = {}
    for s in (int(x) for x in args.seqs.split(",")):
        # hold total tokens ~constant across seq lengths (the bench
        # leg shape): bh 96 @2048 -> 48 @4096 -> 24 @8192
        bh, d = max(12, args.bh * 2048 // s), args.d
        q = jnp.asarray(rng.randn(bh, s, d), jnp.bfloat16)
        k = jnp.asarray(rng.randn(bh, s, d), jnp.bfloat16)
        v = jnp.asarray(rng.randn(bh, s, d), jnp.bfloat16)
        out, lse = jax.jit(functools.partial(
            fa._flash_fwd, scale=scale, causal=causal))(q, k, v)
        jax.block_until_ready((out, lse))

        mm = 2.0 * bh * s * s * d  # dense FLOPs of one score-sized matmul

        def xla_attn(qc):
            sc = jnp.einsum("bqd,bkd->bqk", qc, k).astype(jnp.float32) \
                * scale
            return jnp.einsum("bqk,bkd->bqd",
                              jax.nn.softmax(sc, -1).astype(v.dtype), v)

        def flash_fwd(qc):
            return fa.flash_attention(qc, k, v, scale, causal)

        def flash_loss(qc, kc, vc):
            return jnp.sum(
                fa.flash_attention(qc, kc, vc, scale, causal)
                .astype(jnp.float32) ** 2)

        grad_all = jax.grad(flash_loss, argnums=(0, 1, 2))

        def flash_fwd_bwd(qc):
            # consume ALL THREE gradients: grad wrt q alone lets JAX
            # dead-code-eliminate the dkv pallas kernel entirely (it
            # did, inflating the r5 first-capture utilization ~1.7x)
            dq, dk, dv = grad_all(qc, k, v)
            return dq + 0.0 * (dk + dv).astype(dq.dtype)

        leg = {}
        score_bytes = bh * s * s * 4
        if score_bytes < spec.hbm_capacity // 4:
            dt = timed(xla_attn, q)
            leg["xla_attention"] = {
                "ms": round(dt * 1e3, 3),
                "dense_util": round(2 * mm / dt / peak, 4)}
        else:  # unfused scores would not even fit — flash's raison d'etre
            leg["xla_attention"] = {
                "error": f"scores {score_bytes/1e9:.1f} GB exceed HBM"}
        dt = timed(flash_fwd, q)
        leg["flash_fwd"] = {"ms": round(dt * 1e3, 3),
                            "dense_util": round(2 * mm / dt / peak, 4),
                            "blocks": fa._pick_blocks("fwd", s, s)}
        dt = timed(flash_fwd_bwd, q)
        leg["flash_fwd_bwd"] = {
            "ms": round(dt * 1e3, 3),
            "dense_util": round(9 * mm / dt / peak, 4),
            "dq_blocks": fa._pick_blocks("dq", s, s),
            "dkv_blocks": fa._pick_blocks("dkv", s, s),
        }
        results[str(s)] = leg
        print(f"seq{s}: {leg}", file=sys.stderr)

    print(json.dumps({
        "workload": f"flash kernels bh{args.bh} d{args.d} bf16 "
                    f"causal={causal} (dense-FLOP utilization vs "
                    f"nominal peak)",
        "peak_flops": peak, "hbm_bandwidth": hbm,
        "calibration_matmul_tfs": round(matmul_tfs, 1),
        "seqs": results,
    }))


if __name__ == "__main__":
    main()
