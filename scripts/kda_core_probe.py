#!/usr/bin/env python3
"""The delta rule's core alone, forward and backward, at a training
step's shapes, fed as `KimiDeltaAttention` feeds it: q~, k~, v as the
convs leave them and one decay a channel, all flat `[b, s, h d]`
float32, the l2norm of q~ and k~ the core's own (`CHUNKED_RULES`'
signature and layout); bf16 operands for the walk's products.
Variants: the jax.numpy rule by chunk and sub-chunk length
(`--chunks`), and `ops/pallas/chunked_delta_rule.delta_rule_chunked_kernel`
(the same rule as Pallas kernels) by the heads a grid program of its
walk over the chunks holds (`--kernel-heads`), each twice: handed over
flat as the convs leave them (the op's), and through the by-head form
first (`[b, s, h, d]` and the l2norm in jax.numpy, as the op did before
PR 45, then flat again for the rule: what the by-head form costs a
caller on the chip, a copy each way and the norm's passes).
Against the floors the benchmark holds
the core to (`families/kimi_linear.kda_core_flops` at the bf16 peak and
`kda_core_bytes` at the published bandwidth, for ONE layer).  Prints ms
a call on the chip and the compiler's temporaries
(`chiprun -- python3 scripts/kda_core_probe.py`); `--compile-only`
compiles every variant for a described v5e in the sandbox and prints no
time.  `--scan` adds the scan a position at a shorter length (its
backward keeps every position's state, so the cell's length does not
fit).  The numbers PERF.md quotes for the choice of C (section 6,
PR 43) and of the heads a program (PR 44).
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.families import kimi_linear as fam  # noqa: E402
from flexflow_tpu.ops.chunked_delta_rule import (CHUNK_TOKENS,  # noqa: E402
                                                 SUB_CHUNK_TOKENS)
from flexflow_tpu.ops.chunked_delta_rule import unit_heads  # noqa: E402
from flexflow_tpu.ops.gated_delta_net import delta_rule_scan  # noqa: E402
from flexflow_tpu.ops.kimi_delta_attention import head_rms  # noqa: E402
from flexflow_tpu.ops.pallas.chunked_delta_rule import (  # noqa: E402
    CHUNKED_RULES)


def by_head(q, k, v, g, h):
    """The flat operands by head, q~ and k~ through `l2norm`, in
    jax.numpy: on the chip a copy and two passes a tensor."""
    b, s = q.shape[:2]
    q, k, v, g = (t.reshape(b, s, h, -1) for t in (q, k, v, g))
    return (*unit_heads(q, k), v, g)


def core(rule, h, flat=True):
    """The jitted value and gradient of one layer's core under `rule`
    (`CHUNKED_RULES`' signature but for chunk and sub-chunk, or the
    scan's by-head one) from a zero state, fed flat; `flat=False`:
    through `by_head` first, and flat again for a rule of the table."""
    table = rule is not delta_rule_scan

    def loss(q, k, v, g, beta, probe):
        b, s, hd = q.shape
        S = jnp.zeros((b, h, hd // h, hd // h), jnp.float32)
        if not flat:
            q, k, v, g = by_head(q, k, v, g, h)
            if table:
                q, k, v, g = (t.reshape(b, s, hd) for t in (q, k, v, g))
        _, o = rule(S, q, k, v, g, beta)
        return jnp.sum(o.astype(jnp.float32).reshape(probe.shape) * probe)

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))


def values(b, s, h, d):
    ks = jax.random.split(jax.random.key(0), 6)
    q, k, v = (jax.nn.silu(jax.random.normal(key, (b, s, h * d)))
               for key in ks[:3])
    # a channel forgets over tens to thousands of positions
    g = -jnp.exp(jax.random.uniform(ks[3], (b, s, h * d), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1))) * 4.0
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    probe = jax.random.normal(ks[5], (b, s, h * d))
    return [q, k, v, g, beta, probe]


def output_side(h, eps, flat=True):
    """What `KimiDeltaAttention` does with the core's output `o` (the
    compute precision), value and gradient: the head norm, the gate and
    the output projection.  `flat`: the op's, `head_rms` on `[b, s,
    h d]`; else by head as before PR 45 (o `[b, s, h, d]`, the gate
    reshaped to it, y reshaped back for the product)."""
    f32 = jnp.float32

    def loss(o, gate, norm_w, w_o, probe):
        o = o.astype(f32)
        if flat:
            y = head_rms(o, h, eps) * jnp.tile(norm_w, h) \
                * jax.nn.sigmoid(gate)
        else:
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1,
                                           keepdims=True) + eps)
            y = (o * norm_w * jax.nn.sigmoid(gate.reshape(o.shape))) \
                .reshape(gate.shape)
        y = jax.lax.optimization_barrier(y.astype(w_o.dtype))
        return jnp.sum(jnp.matmul(y, w_o).astype(f32) * probe)

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)))


def output_values(b, s, h, d, e, flat=True):
    ks = jax.random.split(jax.random.key(1), 4)
    bf = jnp.bfloat16
    o = jax.random.normal(ks[0], (b, s, h * d) if flat else (b, s, h, d))
    return [o.astype(bf), jax.random.normal(ks[1], (b, s, h * d)),
            jnp.ones((d,)), (jax.random.normal(ks[2], (h * d, e))
                             * (h * d) ** -0.5).astype(bf),
            jax.random.normal(ks[3], (b, s, e))]


def by_kind(compiled, vals, top: int) -> str:
    """One traced call's device operations: summed by name (copies of
    one fusion merged), the `top` largest as "name ms (events)"; then
    the `top` largest single operations, each with the result it has
    in the compiled program's text."""
    import collections
    import glob
    import re
    import tempfile

    from benchmarks import reduce_trace as rt

    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            jax.block_until_ready(compiled(*vals))
        planes = rt.read_planes(glob.glob(
            os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))[0])
    if not planes:
        return "the trace holds no TPU plane"
    plane = planes[0]
    ms, events, each = (collections.Counter(), collections.Counter(),
                        collections.Counter())
    for name, start, end in plane["ops"]:
        if rt.stem(name) not in rt.ENVELOPES:
            ms[rt.stem(name)] += 1e3 * (end - start)
            events[rt.stem(name)] += 1
            each[rt.short_name(name)] += 1e3 * (end - start)
    result = dict(re.findall(r"%?([\w.-]+) = (\(?[a-z0-9]+\[[^ ]*) ",
                             compiled.as_text()))
    return ("; ".join(f"{n} {v:.2f} ms ({events[n]})"
                      for n, v in ms.most_common(top))
            + "\n  single operations: " + "; ".join(
                f"{n} {result.get(n, '?')} {v:.2f} ms"
                for n, v in each.most_common(top)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=os.path.join(
        ROOT, "benchmarks", "configs", "kimi-linear-ep32-train.json"),
        help="the configuration whose KDA heads are run")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--chunks", default="32:16,64:16,64:32,128:16,128:32",
                    help="chunk:sub-chunk pairs to run")
    ap.add_argument("--kernel-heads", default="8",
                    help="the Pallas kernels, a variant each: heads a grid "
                         "program of the walk over chunks holds (the op's "
                         "chunk and sub-chunk); empty: no kernel variant")
    ap.add_argument("--output-side", action="store_true",
                    help="also the op's norm_gate and out, fed o flat "
                         "(the op's) and by head (as before PR 45)")
    ap.add_argument("--scan", type=int, default=0,
                    help="also the scan a position, at this many positions")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--by-kind", type=int, default=0,
                    help="also trace one call of each variant and print "
                         "its N largest device operations by name")
    ap.add_argument("--compile-only", action="store_true")
    args = ap.parse_args()
    with open(args.config) as f:
        cfg = json.load(f)
    lin = cfg["linear_attn_config"]
    b, s, h, d = args.batch, args.seq, lin["num_heads"], lin["head_dim"]
    bf = jnp.bfloat16
    pairs = [tuple(int(n) for n in p.split(":"))
             for p in filter(None, args.chunks.split(","))]
    def fed(seq):
        return functools.partial(values, b, seq, h, d)

    # name -> (jitted value and gradient, positions, what makes its values)
    variants = {
        f"chunked C={c} sub={sub}"
        + (" (the op's off the chip)"
           if (c, sub) == (CHUNK_TOKENS, SUB_CHUNK_TOKENS) else ""):
        (core(functools.partial(CHUNKED_RULES["chunked"], chunk=c, sub=sub,
                                operand_dtype=bf), h), s, fed(s))
        for c, sub in pairs}
    for hb in filter(None, args.kernel_heads.split(",")):
        # off the chip (--compile-only) the kernels are lowered for
        # Mosaic all the same: `interpret` is the default's only there
        kernels = functools.partial(
            CHUNKED_RULES["chunked_kernel"], chunk=CHUNK_TOKENS,
            sub=SUB_CHUNK_TOKENS, operand_dtype=bf, heads_block=int(hb),
            interpret=False)
        name = (f"kernels C={CHUNK_TOKENS} sub={SUB_CHUNK_TOKENS} heads a "
                f"program of the walk={hb}, ")
        variants[name + "flat, l2norm in the kernels (the op's)"] = (
            core(kernels, h), s, fed(s))
        variants[name + "by head and l2norm in jax.numpy first"] = (
            core(kernels, h, flat=False), s, fed(s))
    if args.scan:
        variants[f"scan a position, {args.scan} positions"] = (
            core(delta_rule_scan, h, flat=False), args.scan,
            fed(args.scan))
    if args.output_side:
        for flat, name in ((True, "flat, head_rms's two products (the op's)"),
                           (False, "by head, reshapes around the norm")):
            variants["norm_gate and out, " + name] = (
                output_side(h, cfg["rms_norm_eps"], flat), s,
                functools.partial(output_values, b, s, h, d,
                                  cfg["hidden_size"], flat))

    # the floors of ONE layer, by the benchmark's own count
    layers = len(lin["kda_layers"])
    with open(os.path.join(ROOT, "benchmarks", "peaks.json")) as f:
        peak = next(iter(json.load(f)["devices"].values()))

    def floors(seq):
        return (fam.kda_core_flops(cfg, b, seq) / layers
                / peak["bf16_flops_per_s"],
                fam.kda_core_bytes(cfg, b, seq) / layers
                / peak["hbm_bytes_per_s"])

    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sh = SingleDeviceSharding(topo.devices[0])
        for name, (fn, seq, make) in variants.items():
            structs = [jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sh)
                       for v in jax.eval_shape(make)]
            t0 = time.monotonic()
            m = fn.trace(*structs).lower(
                lowering_platforms=("tpu",)).compile().memory_analysis()
            print(f"{name}: compiled in {time.monotonic() - t0:.0f} s; "
                  f"temporaries {m.temp_size_in_bytes / 1e9:.2f} GB",
                  flush=True)
        return 0

    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "batch": b, "seq": s, "heads": h, "head_dim": d}),
          flush=True)
    for name, (fn, seq, make) in variants.items():
        vals = make()
        try:
            t0 = time.monotonic()
            compiled = fn.lower(*vals).compile()
            first = time.monotonic() - t0
            jax.block_until_ready(compiled(*vals))
            t0 = time.monotonic()
            for _ in range(args.iters):
                got = compiled(*vals)
            jax.block_until_ready(got)
            ms = 1e3 * (time.monotonic() - t0) / args.iters
        except Exception as e:  # a variant that does not fit is a finding
            print(f"{name}: FAILED {type(e).__name__}: "
                  f"{str(e).splitlines()[0][:200]}", flush=True)
            continue
        if args.by_kind:
            print("  " + by_kind(compiled, vals, args.by_kind), flush=True)
        by_flops, by_bytes = floors(seq)
        least = max(by_flops, by_bytes)
        finite = all(bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))
                     for x in jax.tree.leaves(got))
        print(f"{name}: {ms:.2f} ms a call forward + backward "
              f"({1e3 * ms / seq:.2f} us a position; compiled in "
              f"{first:.0f} s; temporaries "
              f"{compiled.memory_analysis().temp_size_in_bytes / 1e9:.2f} "
              f"GB; finite {finite}); least {1e3 * by_flops:.2f} ms by "
              f"operations, {1e3 * by_bytes:.2f} ms by bytes: "
              f"{100 * least / (ms / 1e3):.1f} % of the floor's rate",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
