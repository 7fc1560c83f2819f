#!/usr/bin/env python3
"""One routed-expert layer, forward and backward, at a training step's
shapes (`--cell lfm2`: cell 6's, `--cell kimi`: cell 8's): the dense
product against the grouped one (alone, and as a checkpointed segment
of the executor's keeping nothing or its products), the grouped one's
matrix products as libtpu's ragged dot against the grouped-matmul
kernel at a grid of tilings (`--sweep`: each of the three products
alone, then the whole layer at the tilings `--layer-tilings` names),
and the step that overflows the usual buffers.  Prints ms a call on the
chip (`chiprun -- python3 scripts/expert_product_probe.py`), then the
checkpointed layer's device time by part (`benchmarks/device_scopes.py`
over a trace of its own); `--compile-only` compiles every variant (and,
with `--sweep`, every tiling of the grid) for a described v5e in the
sandbox and prints no time (`--hlo DIR` also writes each variant's
optimized HLO there).
"""
from __future__ import annotations

import argparse
import functools
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
#: cell 6's and cell 8's routed layer
CELLS = {"lfm2": dict(rows=8192, hidden=2048, width=1792, held=8, total=32,
                      top_k=4),
         "kimi": dict(rows=8192, hidden=2304, width=1024, held=8, total=256,
                      top_k=8)}
PICKER = rx.pick_grouped_tiling


def pick_by(plan):
    """A stand-in for `rx.pick_grouped_tiling`: `plan` = "ragged",
    "picked" (the program's own choice for a TPU), one tiling for every
    product, or {(k, n, into_rhs): tiling}."""
    def pick(m, k, n, groups, rows_a_group, backend="", into_rhs=False):
        if plan == "ragged":
            return None
        if plan == "picked":
            return PICKER(m, k, n, groups, rows_a_group, "tpu", into_rhs)
        if isinstance(plan, dict):
            return plan[k, n, into_rhs]
        return plan
    return pick


def tile_grid(k, n, into_rhs):
    """The tilings swept for one product: row tiles of 128-512, the
    contracted and the output width whole, halved, in thirds where 128
    lanes divide that, and the 1,024 PR 39 tried."""
    def widths(x):
        return sorted({w for w in (x, x // 2, x // 3, 1024)
                       if w <= x and w % 128 == 0 and (
                           x % w == 0 or w == 1024)})
    out = []
    for tm in (128, 256, 512):
        for tk in widths(k):
            for tn in widths(n):
                # double-buffered operand and result tiles and the
                # float32 accumulator, against the 16 MiB a kernel may
                # take: a loose bound, Mosaic refuses what does not fit
                if into_rhs:
                    vmem = 4 * tm * (tk + tn) + 8 * tk * tn
                else:
                    vmem = 4 * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn
                if vmem <= 16 * 2 ** 20:
                    out.append((tm, tk, tn))
    return out


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


def products_of(shape):
    """{name: (function, lhs shape, rhs shape, calls a step's layer
    makes, the kernel's (k, n, into_rhs))} of the six products a routed
    layer's forward and backward make, at the usual buffers' size."""
    t, e, f, n = (shape[x] for x in ("rows", "hidden", "width", "held"))
    m = usual_slots(shape)
    mm, into_lhs, into_rhs = (getattr(rx, name) for name in MATMULS)
    return m, {
        "gate_up": (mm, (m, e), (n, e, f), 2, (e, f, False)),
        "down": (mm, (m, f), (n, f, e), 1, (f, e, False)),
        "d_act": (into_lhs, (m, e), (n, f, e), 1, (e, f, False)),
        "d_xs": (into_lhs, (m, f), (n, e, f), 2, (f, e, False)),
        "d_w_gate_up": (into_rhs, (m, e), (m, f), 2, (e, f, True)),
        "d_w_down": (into_rhs, (m, f), (m, e), 1, (f, e, True)),
    }


def usual_slots(shape):
    pairs = shape["rows"] * shape["top_k"] * shape["held"] / shape["total"]
    return rx.grouped_slots(shape["rows"] * shape["top_k"], pairs,
                            shape["hidden"], shape["width"],
                            shape["held"])[0]


#: calls of a product one timed program makes, each on operands of its
#: own: a product of cell 8 takes ~0.1 ms, a dispatch from the host as
#: long
CALLS_A_PROGRAM = 8


def sweep(shape, iters, structs=None):
    """ms a call of each product alone under the ragged dot and under
    every tiling of its grid (`structs`: compile only, for the sharding
    it names) -> {product: {plan: ms or error}}, the best plan of each."""
    import numpy as np

    m, products = products_of(shape)
    n = shape["held"]
    pairs = shape["rows"] * shape["top_k"] * n // shape["total"]
    sizes = jnp.asarray(np.random.default_rng(0).multinomial(
        pairs, [1.0 / n] * n), jnp.int32)
    keys = jax.random.split(jax.random.key(2), 2)
    out, best = {}, {}
    for name, (fn, lhs_shape, rhs_shape, calls, key) in products.items():
        out[name] = {"calls_a_layer": calls}
        both = fn is rx.grouped_matmul_into_rhs  # a gradient a call
        if structs is None:
            lhs = jax.random.normal(
                keys[0], (CALLS_A_PROGRAM,) + lhs_shape, jnp.bfloat16)
            rhs = jax.random.normal(
                keys[1], (CALLS_A_PROGRAM,) * both + rhs_shape, jnp.bfloat16)
        for plan in ["ragged"] + tile_grid(*key):
            rx.pick_grouped_tiling = pick_by(plan)

            def calls(lhs, rhs, sizes, fn=fn, both=both):
                return tuple(fn(lhs[i], rhs[i] if both else rhs, sizes,
                                pairs / n) for i in range(CALLS_A_PROGRAM))

            jitted = jax.jit(calls)
            label = plan if plan == "ragged" else "x".join(map(str, plan))
            try:
                if structs is not None:
                    jitted.trace(
                        structs((CALLS_A_PROGRAM,) + lhs_shape),
                        structs((CALLS_A_PROGRAM,) * both + rhs_shape),
                        structs((n,), jnp.int32)).lower(
                        lowering_platforms=("tpu",)).compile()
                    out[name][label] = "compiled"
                else:
                    got = jax.block_until_ready(jitted(lhs, rhs, sizes))
                    t0 = time.monotonic()
                    for _ in range(iters):
                        got = jitted(lhs, rhs, sizes)
                    jax.block_until_ready(got)
                    out[name][label] = 1e3 * (time.monotonic() - t0) / (
                        iters * CALLS_A_PROGRAM)
            except Exception as ex:  # a tiling Mosaic refuses
                out[name][label] = f"{type(ex).__name__}: {str(ex)[:160]}"
            print(name, label, out[name][label], flush=True)
        picked = PICKER(m, key[0], key[1], n, pairs / n, "tpu", key[2])
        out[name]["picked"] = picked and "x".join(map(str, picked))
        timed = {k: v for k, v in out[name].items()
                 if isinstance(v, float) and k != "ragged"}
        if timed:
            label = min(timed, key=timed.get)
            best[key] = tuple(map(int, label.split("x")))
            out[name]["best"] = label
    rx.pick_grouped_tiling = PICKER
    return out, best


def tails(m, e, f, n, count, plan):
    """What this backend's three products, under `plan` (`pick_by`), do
    with the rows past the
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
    rx.pick_grouped_tiling = pick_by(plan)
    mm, into_lhs, into_rhs = (jax.jit(functools.partial(
        getattr(rx, name), rows_a_group=count / n)) for name in MATMULS)
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
    rx.pick_grouped_tiling = PICKER
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
    ap.add_argument("--cell", choices=sorted(CELLS), default="lfm2")
    for name in CELLS["lfm2"]:
        ap.add_argument("--" + name.replace("_", "-"), type=int)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--sweep", action="store_true",
                    help="each product alone by tiling, before the layer")
    ap.add_argument("--layer-tilings", nargs="*", default=[],
                    help="tm,tk,tn: the whole layer with every product "
                    "at that tiling")
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--no-layer", action="store_true",
                    help="the sweep alone")
    ap.add_argument("--hlo", default="")
    args = ap.parse_args()
    shape = {k: getattr(args, k) or v for k, v in CELLS[args.cell].items()}
    p = rx.RoutedExpertsParams(
        experts_total=shape["total"], experts_held=shape["held"],
        first_held=0, top_k=shape["top_k"], expert_hidden=shape["width"],
        norm_eps=1e-6)
    t, e, f, n = (shape[x] for x in ("rows", "hidden", "width", "held"))
    bf = jnp.bfloat16
    shapes = [((t, e), bf), ((e, shape["total"]), jnp.float32),
              ((shape["total"],), jnp.float32), ((n, e, f), bf),
              ((n, e, f), bf), ((n, f, e), bf), ((t, e), jnp.float32)]
    # name: (product, the grouped products' plan (`pick_by`), what a
    # segment keeps, slack)
    variants = {
        "dense": ("dense", "ragged", None, None),
        "grouped.ragged_dot": ("grouped", "ragged", None, None),
        "grouped.kernel_picked": ("grouped", "picked", None, None),
        "grouped.ragged_dot.remat_none": ("grouped", "ragged", "none", None),
        "grouped.ragged_dot.remat_products":
            ("grouped", "ragged", "products", None),
        "grouped.kernel_picked.remat_products":
            ("grouped", "picked", "products", None),
        # a step whose held pairs overflow the usual buffers: the
        # every-pair size, whose backward runs its forward again
        "grouped.ragged_dot.remat_products.overflow":
            ("grouped", "ragged", "products", 0.5),
        "grouped.kernel_picked.remat_products.overflow":
            ("grouped", "picked", "products", 0.5),
    }
    for text in args.layer_tilings:
        variants["grouped.kernel_" + text.replace(",", "x")] = (
            "grouped", tuple(map(int, text.split(","))), None, None)

    def build(name):
        product, plan, keep, slack = variants[name]
        rx.pick_grouped_tiling = pick_by(plan)
        return layer(product, p, keep, slack)

    out = {"shapes": shape}
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sh = SingleDeviceSharding(topo.devices[0])
        jax.default_backend = lambda: "tpu"  # Mosaic, not the interpreter

        def struct(s, d=bf):
            return jax.ShapeDtypeStruct(s, d, sharding=sh)

        if args.sweep:
            out["sweep"], _ = sweep(shape, args.iters, struct)
        for name in () if args.no_layer else variants:
            t0 = time.monotonic()
            c = build(name).trace(*(struct(s, d) for s, d in shapes)).lower(
                lowering_platforms=("tpu",)).compile()
            m = c.memory_analysis()
            print(name, "compiled in %.1f s; temp bytes" % (
                time.monotonic() - t0), m.temp_size_in_bytes, flush=True)
            if args.hlo:
                os.makedirs(args.hlo, exist_ok=True)
                with open(os.path.join(args.hlo, name + ".hlo"), "w") as fh:
                    fh.write(c.as_text())
        return 0

    keys = jax.random.split(jax.random.key(0), len(shapes))
    vals = [(0.02 if i else 1.0) * jax.random.normal(k, s, jnp.float32)
            .astype(d) for i, (k, (s, d)) in enumerate(zip(keys, shapes))]
    want = None
    out["device"] = jax.devices()[0].device_kind
    m_usual = usual_slots(shape)
    out["tails"] = {plan: tails(m_usual, e, f, n, m_usual * 2 // 3 + 5, plan)
                    for plan in ("ragged", "picked")}
    print("tails", json.dumps(out["tails"]), flush=True)
    if args.sweep:
        out["sweep"], best = sweep(shape, args.iters)
        variants["grouped.kernel_best_of_sweep"] = (
            "grouped", best, None, None)
        variants["grouped.kernel_best_of_sweep.remat_products"] = (
            "grouped", best, "products", None)
        print("best", json.dumps({str(k): v for k, v in best.items()}),
              flush=True)
    for name in () if args.no_layer else variants:
        fn = build(name)
        try:
            t0 = time.monotonic()
            got = jax.block_until_ready(fn(*vals))
            first = time.monotonic() - t0
        except Exception as ex:  # a variant the chip refuses
            out[name] = f"{type(ex).__name__}: {str(ex)[:200]}"
            print(name, out[name], flush=True)
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
        out[name] = {"ms": ms, "grad_rel_l2_vs_dense": err,
                     "first_call_s": first}
        print(name, json.dumps(out[name]), flush=True)
    # where the checkpointed layer's time goes, by the program's names
    # (a device plane: only a chip's trace has one)
    for name in [v for v in variants if v.endswith(".remat_products")
                 or v.endswith(".overflow")
                 ] if jax.default_backend() == "tpu" else ():
        if not isinstance(out.get(name), dict):
            continue
        fn = build(name)
        jax.block_until_ready(fn(*vals))
        out[name + ".by_part"] = by_part(fn, vals, args.iters)
        print(name, "\n".join(out[name + ".by_part"]), sep="\n", flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/expert_product_probe.{args.cell}.json",
              "w") as fh:
        json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
