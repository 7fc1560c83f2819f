#!/usr/bin/env python3
"""One routed-expert layer, forward and backward, at a training step's
shapes: the dense product against the grouped one, and the grouped
one's matrix product as libtpu's ragged dot against the megablox Pallas
kernel.  Prints ms a call on the chip (`chiprun -- python3
scripts/expert_product_probe.py`); `--compile-only` compiles every
variant for a described v5e in the sandbox and prints no time.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from flexflow_tpu.ops import routed_experts as rx  # noqa: E402


def layer(product, p, keep=None):
    def loss(h, router, bias, wg, wu, wd, target):
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
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 3, 4, 5)))


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
    args = ap.parse_args()
    p = rx.RoutedExpertsParams(
        experts_total=args.total, experts_held=args.held, first_held=0,
        top_k=args.top_k, expert_hidden=args.width, norm_eps=1e-6)
    t, e, f, n = args.rows, args.hidden, args.width, args.held
    bf = jnp.bfloat16
    shapes = [((t, e), bf), ((e, args.total), jnp.float32),
              ((args.total,), jnp.float32), ((n, e, f), bf), ((n, e, f), bf),
              ((n, f, e), bf), ((t, e), jnp.float32)]

    from jax.experimental.pallas.ops.tpu.megablox import ops as mb

    def megablox(tiling):
        def mm(lhs, rhs, sizes):
            return mb.gmm(lhs, rhs, sizes, lhs.dtype, tiling)
        return mm

    variants = {"dense": ("dense", None),
                "grouped.ragged_dot": ("grouped", rx.grouped_matmul),
                "grouped.megablox_512_1024_1024":
                    ("grouped", megablox((512, 1024, 1024))),
                "grouped.megablox_512_512_512":
                    ("grouped", megablox((512, 512, 512)))}
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sh = SingleDeviceSharding(topo.devices[0])
        structs = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d in shapes]
        for name, (product, mm) in variants.items():
            if mm is not None:
                rx.grouped_matmul = mm
            c = layer(product, p).trace(*structs).lower(
                lowering_platforms=("tpu",)).compile()
            m = c.memory_analysis()
            print(name, "compiled; temp bytes", m.temp_size_in_bytes,
                  flush=True)
        return 0

    keys = jax.random.split(jax.random.key(0), len(shapes))
    vals = [(0.02 if i else 1.0) * jax.random.normal(k, s, jnp.float32)
            .astype(d) for i, (k, (s, d)) in enumerate(zip(keys, shapes))]
    want = None
    out = {"device": jax.devices()[0].device_kind, "shapes": vars(args)}
    # the shipped product again as a checkpointed segment (PR 37): what
    # the segment keeps, and with the slots' gather kept beside it
    variants["grouped.ragged_dot.remat_none"] = (
        "grouped", rx.grouped_matmul, "none")
    variants["grouped.ragged_dot.remat_products"] = (
        "grouped", rx.grouped_matmul, "products")
    variants["grouped.ragged_dot.remat_products+gather"] = (
        "grouped", rx.grouped_matmul, "products+gather")
    variants["grouped.ragged_dot.remat_products+sort"] = (
        "grouped", rx.grouped_matmul, "products+sort")
    variants["grouped.ragged_dot.remat_products+sort+route"] = (
        "grouped", rx.grouped_matmul, "products+sort+route")
    rows_to_slots, argsort, route = rx._rows_to_slots, jnp.argsort, rx.route
    for name, (product, mm, *keep) in variants.items():
        if mm is not None:
            rx.grouped_matmul = mm
        keep, *also = keep[0].split("+") if keep else (None,)
        rx._rows_to_slots, jnp.argsort, rx.route = rows_to_slots, argsort, route
        if "gather" in also:
            rx._rows_to_slots = lambda *a: rx.remat_keep(rows_to_slots(*a))
        if "sort" in also:  # the two permutations, 128 KB each
            jnp.argsort = lambda *a, **kw: rx.remat_keep(argsort(*a, **kw))
        if "route" in also:  # the chosen experts and their weights
            rx.route = lambda *a: tuple(map(rx.remat_keep, route(*a)))
        fn = layer(product, p, keep)
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
    rx._rows_to_slots, jnp.argsort, rx.route = rows_to_slots, argsort, route
    # does the ragged product's time follow the rows in its groups?
    lhs = vals[0].repeat(args.top_k, axis=0)  # [t * k, e]
    for share in (0.25, 1.0):
        sizes = jnp.full((n,), int(lhs.shape[0] * share) // n, jnp.int32)
        fn = jax.jit(functools.partial(jax.lax.ragged_dot))
        jax.block_until_ready(fn(lhs, vals[3], sizes))
        t0 = time.monotonic()
        for _ in range(args.iters):
            r = fn(lhs, vals[3], sizes)
        jax.block_until_ready(r)
        out[f"ragged_dot.rows_in_groups_{share}"] = \
            1e3 * (time.monotonic() - t0) / args.iters
    # the gather that fills the usual buffers, alone: what a backward
    # pass that does not keep it pays again
    m = -(-int(rx.GROUPED_SLACK * t * args.top_k * n / args.total)
          // rx.GROUPED_ROW_TILE) * rx.GROUPED_ROW_TILE
    order = jax.random.permutation(keys[0], t * args.top_k).astype(jnp.int32)
    slot_of = jnp.argsort(order).reshape(t, args.top_k).astype(jnp.int32)
    fn = jax.jit(lambda h, o, s: rows_to_slots(h, o[:m], s))
    jax.block_until_ready(fn(vals[0], order, slot_of))
    t0 = time.monotonic()
    for _ in range(args.iters):
        r = fn(vals[0], order, slot_of)
    jax.block_until_ready(r)
    out["rows_to_slots.gather_ms"] = \
        1e3 * (time.monotonic() - t0) / args.iters
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/expert_product_probe.json", "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
