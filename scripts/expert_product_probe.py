#!/usr/bin/env python3
"""One routed-expert layer, forward and backward, at a training step's
shapes: the dense product against the grouped one (alone, and as a
checkpointed segment of the executor's keeping nothing or its
products), the grouped one's matrix products as libtpu's ragged dot
against the megablox Pallas kernels, and the step that overflows the
usual buffers.  Prints ms a call on the chip (`chiprun -- python3
scripts/expert_product_probe.py`), then the checkpointed layer's device
time by part (`benchmarks/device_scopes.py` over a trace of its own);
`--compile-only` compiles every variant for a described v5e in the
sandbox and prints no time (`--hlo DIR` also writes each variant's
optimized HLO there).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from flexflow_tpu.obs.scopes import scope  # noqa: E402
from flexflow_tpu.ops import routed_experts as rx  # noqa: E402

MATMULS = ("grouped_matmul", "grouped_matmul_into_lhs",
           "grouped_matmul_into_rhs")


def layer(product, p, keep=None, slack=None):
    """The jitted value and gradient of one layer under `product`;
    `slack` stands in for `GROUPED_SLACK` while it is traced."""
    def loss(h, router, bias, wg, wu, wd, target):
        with scope("route"):
            chosen, w = rx.route(h, router, bias, p)
        at = chosen - p.first_held
        on = (at >= 0) & (at < p.experts_held)
        if product == "dense":
            landed = jax.nn.one_hot(at, p.experts_held, dtype=jnp.float32)
            out = rx.dense_experts(
                h, jnp.einsum("tkx,tk->tx", landed, w), wg, wu, wd)
        else:
            out, _ = rx.grouped_experts(
                h, jnp.where(on, at, p.experts_held), w, wg, wu, wd,
                h.shape[0] * p.top_k * p.experts_held / p.experts_total)
        return jnp.sum(out.astype(jnp.float32) * target)

    if keep is not None:
        # the layer as a checkpointed segment of the executor's
        from flexflow_tpu.executor import _REMAT_POLICIES

        loss = jax.checkpoint(loss, policy=_REMAT_POLICIES[keep])

    def named(*args):
        usual = rx.GROUPED_SLACK
        rx.GROUPED_SLACK = usual if slack is None else slack
        try:
            with scope("RoutedExperts", "probe"):
                return jax.value_and_grad(loss, argnums=(0, 1, 3, 4, 5))(
                    *args)
        finally:
            rx.GROUPED_SLACK = usual

    return jax.jit(named)


def megablox(tiling):
    """`MATMULS` on the megablox kernels at one tiling."""
    import importlib

    # the package's `gmm` is the function; the kernels' module has both
    mb = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")

    def mm(lhs, rhs, sizes):
        return mb.gmm(lhs, rhs, sizes, lhs.dtype, tiling)

    def into_lhs(ct, rhs, sizes):
        return mb.gmm(ct, rhs, sizes, ct.dtype, tiling, transpose_rhs=True)

    def into_rhs(lhs, ct, sizes):
        return mb.tgmm(lhs.swapaxes(0, 1), ct, sizes, lhs.dtype, tiling)

    return mm, into_lhs, into_rhs


def tails(m, e, f, n, count):
    """What this backend's three products do with the rows past the
    last group (`count` of m rows are in the groups, NaN in the rest,
    and NaN where the allocator may hand a result its buffer): whether
    such a row is READ (it must not be: no NaN in a group's rows, nor
    in a weight's slice), and what the first two forms RETURN there
    (zeros, or whatever the buffer held: the layer reads neither)."""
    keys = jax.random.split(jax.random.key(1), 3)
    live = (jnp.arange(m) < count)[:, None]
    lhs = jnp.where(live, jax.random.normal(keys[0], (m, e)), jnp.nan)
    ct = jnp.where(live, jax.random.normal(keys[1], (m, f)), jnp.nan)
    lhs, ct = lhs.astype(jnp.bfloat16), ct.astype(jnp.bfloat16)
    rhs = jax.random.normal(keys[2], (n, e, f)).astype(jnp.bfloat16)
    sizes = jnp.full((n,), count // n, jnp.int32).at[0].add(count % n)
    mm, into_lhs, into_rhs = (jax.jit(getattr(rx, name)) for name in MATMULS)
    out = {}
    for name, fn, x in (("grouped_matmul", mm, lhs),
                        ("grouped_matmul_into_lhs", into_lhs, ct)):
        for shape in ((m, e), (m, f)):  # buffers to be handed out again
            jax.block_until_ready(jnp.full(shape, jnp.nan, jnp.bfloat16))
        got = fn(x, rhs, sizes).astype(jnp.float32)
        out[name] = {
            "reads_past_the_groups": not bool(jnp.all(jnp.isfinite(
                got[:count]))),
            "returns_there": "zeros" if not bool(jnp.any(got[count:]))
            else "unwritten"}
    out["grouped_matmul_into_rhs"] = {"reads_past_the_groups": not bool(
        jnp.all(jnp.isfinite(into_rhs(lhs, ct, sizes).astype(jnp.float32))))}
    return out


def by_part(fn, vals, iters):
    """The by-scope table of `iters` traced calls of `fn`."""
    from benchmarks import device_scopes
    from benchmarks.run import find_xplane

    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        for _ in range(iters):
            got = fn(*vals)
        jax.block_until_ready(got)
        jax.profiler.stop_trace()
        rows, dispatches = device_scopes.reduce(find_xplane(trace_dir))
    with open(os.path.join(os.path.dirname(device_scopes.__file__),
                           "peaks.json")) as f:
        peak = next(iter(json.load(f)["devices"].values()))
    return device_scopes.table(rows, dispatches, peak, least_share=0.002)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=8192)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--width", type=int, default=1792)
    ap.add_argument("--held", type=int, default=8)
    ap.add_argument("--total", type=int, default=32)
    ap.add_argument("--top-k", type=int, default=4)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--hlo", default="")
    args = ap.parse_args()
    p = rx.RoutedExpertsParams(
        experts_total=args.total, experts_held=args.held, first_held=0,
        top_k=args.top_k, expert_hidden=args.width, norm_eps=1e-6)
    t, e, f, n = args.rows, args.hidden, args.width, args.held
    bf = jnp.bfloat16
    shapes = [((t, e), bf), ((e, args.total), jnp.float32),
              ((args.total,), jnp.float32), ((n, e, f), bf), ((n, e, f), bf),
              ((n, f, e), bf), ((t, e), jnp.float32)]
    ragged = tuple(getattr(rx, name) for name in MATMULS)
    # name: (product, the three matmuls, what a segment keeps, slack)
    variants = {
        "dense": ("dense", ragged, None, None),
        "grouped.ragged_dot": ("grouped", ragged, None, None),
        "grouped.megablox_512_1024_1024":
            ("grouped", megablox((512, 1024, 1024)), None, None),
        "grouped.ragged_dot.remat_none": ("grouped", ragged, "none", None),
        "grouped.ragged_dot.remat_products":
            ("grouped", ragged, "products", None),
        # a step whose held pairs overflow the usual buffers: the
        # every-pair size, whose backward runs its forward again
        "grouped.ragged_dot.remat_products.overflow":
            ("grouped", ragged, "products", 0.5),
    }

    def build(name):
        product, matmuls, keep, slack = variants[name]
        for attr, fn in zip(MATMULS, matmuls):
            setattr(rx, attr, fn)
        return layer(product, p, keep, slack)

    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sh = SingleDeviceSharding(topo.devices[0])
        structs = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d in shapes]
        for name in variants:
            c = build(name).trace(*structs).lower(
                lowering_platforms=("tpu",)).compile()
            m = c.memory_analysis()
            print(name, "compiled; temp bytes", m.temp_size_in_bytes,
                  flush=True)
            if args.hlo:
                os.makedirs(args.hlo, exist_ok=True)
                with open(os.path.join(args.hlo, name + ".hlo"), "w") as fh:
                    fh.write(c.as_text())
        return 0

    keys = jax.random.split(jax.random.key(0), len(shapes))
    vals = [(0.02 if i else 1.0) * jax.random.normal(k, s, jnp.float32)
            .astype(d) for i, (k, (s, d)) in enumerate(zip(keys, shapes))]
    want = None
    out = {"device": jax.devices()[0].device_kind, "shapes": vars(args)}
    m_usual = int(rx.GROUPED_SLACK * t * args.top_k * n / args.total)
    out["tails"] = tails(m_usual, e, f, n, m_usual * 2 // 3 + 5)
    print("tails", json.dumps(out["tails"]), flush=True)
    for name in variants:
        fn = build(name)
        try:
            got = jax.block_until_ready(fn(*vals))
        except Exception as ex:  # a variant the chip refuses
            out[name] = f"{type(ex).__name__}: {str(ex)[:200]}"
            continue
        t0 = time.monotonic()
        for _ in range(args.iters):
            got = fn(*vals)
        jax.block_until_ready(got)
        ms = 1e3 * (time.monotonic() - t0) / args.iters
        flat = jnp.concatenate([jnp.ravel(x).astype(jnp.float32)
                                for x in jax.tree.leaves(got[1])])
        if want is None:
            want = flat
        err = float(jnp.linalg.norm(flat - want) / jnp.linalg.norm(want))
        out[name] = {"ms": ms, "grad_rel_l2_vs_dense": err}
        print(name, json.dumps(out[name]), flush=True)
    # where the checkpointed layer's time goes, by the program's names
    # (a device plane: only a chip's trace has one)
    for name in ("grouped.ragged_dot.remat_products",
                 "grouped.ragged_dot.remat_products.overflow"
                 ) if jax.default_backend() == "tpu" else ():
        fn = build(name)
        jax.block_until_ready(fn(*vals))
        out[name + ".by_part"] = by_part(fn, vals, args.iters)
        print(name, "\n".join(out[name + ".by_part"]), sep="\n", flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/expert_product_probe.json", "w") as fh:
        json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
