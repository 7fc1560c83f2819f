#!/usr/bin/env python
"""Where a seq-512 training step's attention core spends its time (PR 33).

Run by hand on the chip; no switch in the program reads anything here.

  --mode step [--flash-min-seq N]
      Cell 1's model (benchmarks/configs/bert-large-train.json, batch 8,
      seq 512, built as benchmarks/drivers/train.py builds it), a few
      traced steps, and the device's operations BY SHAPE: the trace
      names each event with its whole HLO instruction, so a fusion
      family resolves to the tensors it reads and writes.  With
      --flash-min-seq the model is compiled with that FFConfig value
      (what the existing kernels do inside the real step).  --chips 4
      --steps 0 only compiles cell 2's model (batch 8 a chip) and
      prints the `build_step_fns` span's args.
  --mode sweep
      The attention core alone at b 8, h 16, d 64, forward + backward,
      kv 128..1024: the dense path as `_attend` writes it against
      whatever `flash_mha` picks for that length (and the
      long-row kernels through `mha_flash` beside it).

Timing of the sweep: `iters` calls chained in one jitted lax.scan with
real dataflow (scripts/flash_ceiling_probe.py's discipline).  Prints one
JSON line and writes it under chiprun_out/attn_probe/.
"""
import argparse
import collections
import glob
import json
import os
import re
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.join(_HERE, "..")
sys.path.insert(0, _ROOT)
OUT = os.path.join(_ROOT, "chiprun_out", "attn_probe")


def signature(instr: str) -> str:
    """An HLO instruction with its numbering and layouts stripped:
    ``bf16[8,16,512,512] fusion(bf16[8,512,16,64], ...) kLoop``."""
    rest = instr.partition(" = ")[2]
    rest = re.sub(r"\{[^{}]*\}", "", rest)       # layouts / tilings
    rest = re.sub(r"%[\w.\-]+", "", rest)        # operand names
    rest = re.sub(r", calls=.*$", "", rest)
    rest = re.sub(r"\s+", " ", rest).replace(" ,", ",").replace(" )", ")")
    return rest.strip()[:400]


def ops_by_shape(xplane: str, steps: int) -> dict:
    from jax.profiler import ProfileData

    from benchmarks.reduce_trace import ENVELOPES, stem, union_seconds

    fams = collections.defaultdict(
        lambda: {"ms": 0.0, "n": 0,
                 "by": collections.defaultdict(lambda: [0.0, 0])})
    spans = []
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name not in ("XLA Ops", "Async XLA Ops"):
                continue
            for ev in line.events:
                name = stem(ev.name)
                if name in ENVELOPES:
                    continue
                if line.name == "XLA Ops":
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                key = name if line.name == "XLA Ops" else "async:" + name
                f = fams[key]
                f["ms"] += ev.duration_ns * 1e-6
                f["n"] += 1
                s = f["by"][signature(ev.name)]
                s[0] += ev.duration_ns * 1e-6
                s[1] += 1
    rows = []
    for name, f in sorted(fams.items(), key=lambda kv: -kv[1]["ms"])[:28]:
        rows.append({
            "op": name, "ms_a_step": round(f["ms"] / steps, 3),
            "n_a_step": round(f["n"] / steps, 1),
            "shapes": [
                {"ms_a_step": round(ms / steps, 3),
                 "n_a_step": round(n / steps, 1), "instr": sig}
                for sig, (ms, n) in sorted(
                    f["by"].items(), key=lambda kv: -kv[1][0])[:6]],
        })
    busy = union_seconds(spans) * 1e-6
    return {"device_ms_a_step": round(busy / steps, 3), "ops": rows}


def mode_step(args) -> dict:
    import jax
    import numpy as np

    from benchmarks.families import bert
    from flexflow_tpu.obs import trace as obs_trace

    cfg = json.load(open(os.path.join(
        _ROOT, "benchmarks", "configs", args.config)))
    batch, seq = args.batch, args.seq
    batch *= args.chips
    ff = bert.build_model(cfg, batch, seq, args.chips)
    if args.flash_min_seq is not None:
        ff.config.flash_min_seq = args.flash_min_seq
    t0 = time.monotonic()
    bert.compile_model(ff, cfg, jax.devices()[:args.chips])
    compile_s = time.monotonic() - t0
    span_args = [r.args for r in obs_trace.spans()
                 if r.name == "build_step_fns"]
    if not args.steps:  # the compile and what its span says, nothing run
        return {"flash_min_seq": ff.config.flash_min_seq,
                "chips": args.chips, "compile_s": round(compile_s, 2),
                "build_step_fns_args": span_args[-1:] or None}
    ff.set_weights(bert.make_weights(cfg, args.seed, "program"))
    rng = np.random.default_rng(args.seed)
    batches = [bert.make_batch(cfg, batch, seq, rng) for _ in range(4)]
    t0 = time.monotonic()
    jax.block_until_ready(ff.train_step(*batches[0])["loss"])
    first_step_s = time.monotonic() - t0
    for b in batches[1:3]:
        loss = ff.train_step(*b)["loss"]
    jax.block_until_ready(loss)

    n = args.steps
    t0 = time.monotonic()
    losses = [ff.train_step(*batches[i % 4])["loss"] for i in range(n)]
    jax.block_until_ready(losses)
    step_ms = 1e3 * (time.monotonic() - t0) / n

    tdir = os.path.join(OUT, f"trace_{args.tag}")
    jax.profiler.start_trace(tdir)
    losses = [ff.train_step(*batches[i % 4])["loss"]
              for i in range(args.traced_steps)]
    jax.block_until_ready(losses)
    jax.profiler.stop_trace()
    xplane = sorted(glob.glob(os.path.join(
        tdir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    by_shape = ops_by_shape(xplane, args.traced_steps)
    stats = jax.devices()[0].memory_stats() or {}
    return {
        "flash_min_seq": ff.config.flash_min_seq,
        "compile_s": round(compile_s, 2),
        "first_step_s": round(first_step_s, 2),
        "step_ms_host_clock": round(step_ms, 3),
        "tokens_per_s": round(batch * seq / step_ms * 1e3, 1),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "build_step_fns_args": span_args[-1:] or None,
        "loss": float(losses[-1]), **by_shape,
    }


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
    ap.add_argument("--mode", choices=("step", "sweep"), required=True)
    ap.add_argument("--tag", default="")
    ap.add_argument("--config", default="bert-large-train.json")
    ap.add_argument("--flash-min-seq", type=int, default=None)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--seed", type=int, default=3300000001)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--traced-steps", type=int, default=4)
    ap.add_argument("--seqs", default="128,256,512,1024")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--tile-elems", type=int, default=0)
    args = ap.parse_args()
    args.tag = args.tag or (args.mode if args.flash_min_seq is None
                            else f"{args.mode}_fms{args.flash_min_seq}")

    import jax

    dev = jax.devices()[0]
    out = {"probe": args.tag, "platform": dev.platform,
           "device_kind": dev.device_kind,
           **(mode_step(args) if args.mode == "step" else mode_sweep(args))}
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, f"{args.tag}.json"), "w") as f:
        json.dump(out, f, indent=1)
    brief = dict(out)
    if "ops" in brief:  # the whole table is in the file
        brief["ops"] = [{k: r[k] for k in ("op", "ms_a_step", "n_a_step")}
                        for r in brief["ops"][:16]]
    print(json.dumps(brief))


if __name__ == "__main__":
    main()
