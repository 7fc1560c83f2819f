"""Per-operator profiling (reference --profiling + per-kernel cudaEvent
timing, kernels/linear_kernels.cu:95-118, and the search's
inner_measure_operator_cost harness, model.cu:38-75).

TPU-native: each op's forward is jitted standalone on shard-shaped
random inputs and timed with block_until_ready — warmup runs absorb
compile, repeat runs are averaged.  `make_measure_fn` adapts this into
the simulator's OpCostModel measured-override hook so the strategy
search can calibrate against real chip timings.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .fftype import OperatorType
from .ops.op import Op


def _rand_array(shape, dtype, key):
    jd = jnp.dtype(dtype.np_dtype)
    if jnp.issubdtype(jd, jnp.floating):
        return jax.random.normal(key, shape, jd)
    return jnp.zeros(shape, jd)  # int inputs (indices): zeros are in-range


_base_fetch_time_cache: Dict[str, float] = {}


def _base_fetch_time(device=None, refresh: bool = False) -> float:
    """Fixed cost of one jitted-dispatch + hard value fetch — the host
    launch and the device-to-host sync that would otherwise be charged
    to every op; subtracted from chain timings."""
    key = str(device)
    hit = _base_fetch_time_cache.get(key)
    if hit is not None and not refresh:
        return hit
    x = jnp.zeros((8,), jnp.float32)
    if device is not None:
        x = jax.device_put(x, device)
    triv = jax.jit(lambda v: jnp.sum(v))
    float(triv(x))  # compile
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        float(triv(x))  # hard fetch: the only wait that is honest
        best = min(best, time.perf_counter() - t0)
    _base_fetch_time_cache[key] = best
    return best


def measure_op_forward(
    op: Op,
    device=None,
    warmup: int = 1,
    repeats: int = 3,
    shard_shapes: bool = True,
    chain: int = 16,
) -> Optional[float]:
    """Mean forward wall time in seconds of the op's jitted kernel on
    shard-local shapes (one device's share of the work); None when the
    op cannot be profiled standalone (e.g. needs graph context).

    The op runs `chain` times inside one jitted lax.scan whose carry
    passes through an optimization_barrier with the op's output — the
    barrier stops XLA from hoisting the (loop-invariant) op out of the
    loop, and the single hard value fetch at the end is the only
    device wait.  One-shot timings are not used: on a local chip a
    dispatch plus a fetch costs as much as many microsecond kernels,
    and the chain charges that cost once instead of per call.
    """
    # standalone inputs are built on the LOGICAL (NCHW) shapes; a
    # compiled executor may have pinned this op to the physical NHWC
    # layout (pcg/layout.py), so force logical for the measurement
    saved_layout = getattr(op, "_data_layout", None)
    op._data_layout = "nchw"
    try:
        key = jax.random.key(0)
        ins = []
        for i, t in enumerate(op.inputs):
            shp = t.shape.shard_shape if shard_shapes else t.shape.logical_shape
            ins.append(_rand_array(shp, t.shape.dtype, jax.random.fold_in(key, i)))
        ws = []
        for i, spec in enumerate(op.weight_specs):
            shp = (spec.shape.shard_shape if shard_shapes
                   else spec.shape.logical_shape)
            ws.append(_rand_array(shp, spec.shape.dtype,
                                  jax.random.fold_in(key, 100 + i)))
        if not ins:
            return None

        def chained(first, rest, ws, rng):
            def body(x, _):
                out = op.forward([x] + rest, ws, training=False, rng=rng)
                leaf = jax.tree_util.tree_leaves(out)[0]
                # REAL dataflow from this iteration's output into the
                # next iteration's input: a bare optimization_barrier
                # gets split per element by XLA, the unused leaf is
                # DCE'd, and LICM then hoists the loop-invariant op out
                # of the scan — the chain times nothing.  x + 0.0*sum(y)
                # is never folded for floats (NaN semantics).
                eps = 0.0 * jnp.sum(leaf).astype(jnp.float32)
                if jnp.issubdtype(x.dtype, jnp.floating):
                    x2 = x + eps.astype(x.dtype)
                else:
                    x2 = x + eps.astype(jnp.int32).astype(x.dtype)
                return x2, ()

            xn, _ = jax.lax.scan(body, first, None, length=chain)
            out = op.forward([xn] + rest, ws, training=False, rng=rng)
            return jax.tree_util.tree_leaves(out)[0].ravel()[0]

        jfn = jax.jit(chained, static_argnums=())
        if device is not None:
            ins = jax.device_put(ins, device)
            ws = jax.device_put(ws, device)
        rng = jax.random.key(1)
        first, rest = ins[0], list(ins[1:])
        for _ in range(max(1, warmup)):
            float(jfn(first, rest, ws, rng))  # compile + warm caches
        base = _base_fetch_time(device)
        best = float("inf")
        for _ in range(max(1, repeats)):
            t0 = time.perf_counter()
            float(jfn(first, rest, ws, rng))
            best = min(best, time.perf_counter() - t0)
        # chain+1 op executions per call (scan body + final fetch op)
        if best <= base:
            # a stale (load-inflated) cached base can swallow the kernel
            # time; re-measure it once under current conditions
            base = _base_fetch_time(device, refresh=True)
        if best <= base:
            # fetch-latency jitter swallowed the kernel time — a 0 here
            # would be cached as "free" forever; report unmeasurable and
            # let the analytic estimate stand
            return None
        return (best - base) / (chain + 1)
    except Exception as e:  # noqa: BLE001 — the search must keep going
        # an op that cannot run standalone keeps its analytic cost, but
        # never silently: a backend that refuses EVERY op would
        # otherwise look like a calibrated search
        from .logger import calib_logger

        calib_logger.info(
            "measure_op_forward(%s %s) failed, analytic cost stands: "
            "%s: %s", op.op_type.name, op.name, type(e).__name__, e)
        return None
    finally:
        if saved_layout is None:
            del op._data_layout
        else:
            op._data_layout = saved_layout


def make_measure_fn(device=None, warmup: int = 1, repeats: int = 3,
                    chain: int = 16):
    """OpCostModel measure_fn: op -> forward seconds (or None).
    Defaults mirror measure_op_forward's — the chained-scan timing makes
    extra repeats redundant."""

    def fn(op: Op) -> Optional[float]:
        return measure_op_forward(op, device=device, warmup=warmup,
                                  repeats=repeats, chain=chain)

    return fn


_SKIP = {OperatorType.INPUT, OperatorType.WEIGHT, OperatorType.NOOP,
         OperatorType.LOOP_PASSES}


def profile_operators(
    ff, device=None, warmup: int = 2, repeats: int = 5,
) -> List[Dict[str, object]]:
    """Per-op timing table for a compiled FFModel (reference --profiling
    printout).  Rows: name, type, fwd_ms, flops, shard shapes.  An op
    inside a repeated region (`Graph.repeats`) is timed once and its
    row's `fwd_ms` and `flops` are that many times the one call's."""
    graph = ff.operators if ff.operators is not None else ff.layers
    times = graph.repeats()
    rows: List[Dict[str, object]] = []
    for op in graph.topo_order():
        if op.op_type in _SKIP or op.is_parallel_op():
            continue
        t = measure_op_forward(op, device=device, warmup=warmup,
                               repeats=repeats)
        n = times.get(op.name, 1)
        rows.append({
            "name": op.name,
            "type": op.op_type.name,
            "fwd_ms": None if t is None else t * 1e3 * n,
            "flops": op.flops() * n,
            "out_shape": [tuple(o.shape.shard_shape) for o in op.outputs],
        })
    return rows


def print_profile(rows: List[Dict[str, object]]):
    name_w = max((len(str(r["name"])) for r in rows), default=4) + 2
    print(f"{'op':<{name_w}}{'type':<20}{'fwd ms':>10}{'GFLOP':>12}")
    for r in rows:
        ms = "n/a" if r["fwd_ms"] is None else f"{r['fwd_ms']:.3f}"
        gf = r["flops"] / 1e9
        print(f"{r['name']:<{name_w}}{r['type']:<20}{ms:>10}{gf:>12.3f}")
    # unmeasurable ops (fwd_ms None) are EXCLUDED from the total, and
    # the row says so — a sum that silently counted them as 0 ms read
    # as a complete step time when it wasn't
    measured = [r for r in rows if r["fwd_ms"] is not None]
    total = sum(r["fwd_ms"] for r in measured)
    qualifier = f"({len(measured)} measured / {len(rows)} total ops"
    excluded = len(rows) - len(measured)
    if excluded:
        qualifier += f", {excluded} excluded"
    qualifier += ")"
    print(f"{'TOTAL':<{name_w}}{'':<20}{total:>10.3f}  {qualifier}")


# ---------------------------------------------------------------------------
# Region-granularity calibration (fused segments)
# ---------------------------------------------------------------------------

def _merge_regions(raw_segments, ex, max_regions: int):
    """Merge runs of measurable single-tensor segments into at most
    ~max_regions regions (transformer-layer / bottleneck-block size).
    Unmeasurable segments (cache replay, pipeline blocks) break runs
    and are dropped — they stay analytic."""
    group_size = max(1, -(-len(raw_segments) // max_regions))
    regions, run, pending = [], [], 0
    for rseg in raw_segments:
        blocked = any(
            op.op_type == OperatorType.CACHE or op.guid in ex._block_guids
            for op in rseg
        )
        if blocked:
            if run:
                regions.append(run)
            run, pending = [], 0
            continue
        run = run + rseg
        pending += 1
        if pending >= group_size:
            regions.append(run)
            run, pending = [], 0
    if run:
        regions.append(run)
    return regions


def measure_segment_costs(
    ff, device=None, chain: int = 48, repeats: int = 3,
    max_regions: int = 16,
):
    """Measured fwd+bwd seconds for fused regions of a compiled model.

    Standalone per-op timing is blind to XLA fusion context (the r02
    fidelity miss: per-op sums predicted 0.45x..3.6x of the real step),
    and timing every single-tensor segment over-counts the small ones
    (a lone LayerNorm segment materializes boundary cotangents the real
    fused step never writes).  So consecutive pure segments
    (pcg/segments.py boundaries) are merged into ~max_regions regions
    and each region's value_and_grad over its boundary activations and
    member weights is timed, chained through a lax.scan whose next
    input genuinely depends on this iteration's grads; `chain` is sized
    so the measured work dwarfs the one dispatch + fetch it is charged.

    Returns [(member op guids, seconds)] for measured regions; anything
    not covered stays analytic in the simulator.
    """
    from .pcg.layout import NHWC, TO_NHWC_PERM
    from .pcg.segments import external_inputs, split_segments

    ex = ff.executor
    graph = ex.graph
    raw_segments, _ = split_segments(graph)
    regions = _merge_regions(raw_segments, ex, max_regions)
    tensor_by_guid = {t.guid: t for op in graph.ops for t in op.outputs}
    consumed_by: Dict[int, set] = {}
    for op in graph.ops:
        for t in op.inputs:
            consumed_by.setdefault(t.guid, set()).add(op.guid)
    key = jax.random.key(17)
    results = []

    def to_compute(x):
        cd = ex.compute_dtype
        if cd is not None and jnp.issubdtype(x.dtype, jnp.floating) \
                and x.dtype != cd:
            return x.astype(cd)
        return x

    def _measure_region(region, chain_n):
        body_ops = [
            op for op in region
            if op.op_type not in (OperatorType.INPUT, OperatorType.NOOP)
        ]
        moved = sum(
            t.shape.shard_bytes()
            for op in body_ops for t in list(op.outputs) + list(op.weights)
        )
        if not body_ops or (
            moved < (1 << 16) and all(op.flops() <= 0 for op in body_ops)
        ):
            return None, []
        nonlocal key
        in_guids = external_inputs(body_ops)
        in_vals, ok = [], True
        for g in in_guids:
            t = tensor_by_guid.get(g)
            if t is None:
                ok = False
                break
            key, sub = jax.random.split(key)
            v = _rand_array(tuple(t.shape.shard_shape), t.shape.dtype, sub)
            v = to_compute(v)
            if ex._t_layout.get(g) == NHWC and v.ndim == 4:
                v = jnp.transpose(v, TO_NHWC_PERM)
            in_vals.append(v)
        if not ok or not in_vals:
            return None, []
        weights = {
            op.name: ff._weights[op.name]
            for op in body_ops if op.name in ff._weights
        }
        member = {op.guid for op in body_ops}
        # backward seeds only from tensors leaving the region — summing
        # intermediates would add cotangents the real step never has
        out_guids = tuple(
            t.guid for op in body_ops for t in op.outputs
            if consumed_by.get(t.guid, set()) - member
            or not consumed_by.get(t.guid)
        )
        first_is_float = bool(
            jnp.issubdtype(in_vals[0].dtype, jnp.floating)
        )
        if not out_guids or (not first_is_float and not weights):
            return None, []

        def seg_grad(first, rest, w, _ops=tuple(body_ops),
                     _in=tuple(in_guids), _out=out_guids,
                     _diff_first=first_is_float, chain=chain_n):
            def run(first, w):
                env = dict(zip(_in, [first] + list(rest)))
                ctx = {
                    "pipeline_done": True,
                    "weights": {**ff._weights, **w},
                    "state": ff._state,
                    "new_state": {k: dict(v) for k, v in ff._state.items()},
                    "aux": [],
                    "inputs": {},
                    "training": True,
                    "rng": None,
                    "to_compute": to_compute,
                }
                for op in _ops:
                    ex._exec_op(op, env, ctx)
                return sum(
                    jnp.sum(env[g].astype(jnp.float32)) for g in _out
                )

            argnums = (0, 1) if _diff_first else (1,)

            def body(carry, _):
                x, wc = carry
                _, grads = jax.value_and_grad(run, argnums=argnums)(x, wc)
                # REAL dataflow from this iteration's grads into the
                # next iteration's input AND weights: a bare
                # optimization_barrier is not enough (XLA splits the
                # barrier per element, DCEs the unused grad leaf, then
                # LICM hoists what remains), and loop-invariant weights
                # would hoist their casts/prep out of the scan — work
                # the real step pays every step.  x + 0.0*g is never
                # folded for floats (NaN semantics).
                gsum = sum(
                    jnp.sum(g).astype(jnp.float32)
                    for g in jax.tree_util.tree_leaves(grads)
                )
                eps = 0.0 * gsum
                if jnp.issubdtype(x.dtype, jnp.floating):
                    x = x + eps.astype(x.dtype)
                else:
                    x = x + eps.astype(jnp.int32).astype(x.dtype)
                wc = jax.tree_util.tree_map(
                    lambda a: a + eps.astype(a.dtype), wc
                )
                return (x, wc), ()

            (out, _), _ = jax.lax.scan(body, (first, w), None, length=chain)
            return jnp.sum(out.astype(jnp.float32))

        try:
            jfn = jax.jit(seg_grad)
            first, rest = in_vals[0], tuple(in_vals[1:])
            if device is not None:
                first = jax.device_put(first, device)
            float(jfn(first, rest, weights))  # compile + warm
            base = _base_fetch_time(device)
            best = float("inf")
            for _ in range(max(1, repeats)):
                t0 = time.perf_counter()
                float(jfn(first, rest, weights))
                best = min(best, time.perf_counter() - t0)
            if best <= base:
                base = _base_fetch_time(device, refresh=True)
            if best <= base:
                return None, []
            return (best - base) / chain_n, sorted(member)
        except Exception as e:
            # calibration failures flow through the shared logging
            # surface (flexflow_tpu.calib) — the obs TelemetryLogHandler
            # puts them in run_telemetry.jsonl; the full traceback is a
            # DEBUG-level detail
            import traceback

            from .logger import calib_logger

            calib_logger.info(
                "region %s... failed: %r",
                [op.name for op in body_ops][:4], e,
            )
            calib_logger.debug("%s", traceback.format_exc())
            return None, []

    measured_regions = []
    for region in regions:
        t, member = _measure_region(region, chain)
        if t is not None:
            results.append((member, t))
            measured_regions.append(region)

    # Renormalize: sums of per-region chains systematically undershoot
    # the one-program cost (per-cut scheduling/fusion effects the chain
    # cannot see — measured ~0.8 ms/cut on BERT-base).  One measurement
    # of the UNION OF SUCCESSFUL regions with the same harness pins the
    # absolute scale (failed regions stay analytic in the simulator —
    # including them here would charge their cost twice); the regions
    # keep the relative attribution.
    if len(results) > 1:
        whole = [op for r in measured_regions for op in r]
        t_whole, _ = _measure_region(whole, max(8, chain // 4))
        s = sum(c for _, c in results)
        if t_whole is not None and s > 0:
            scale = t_whole / s
            results = [(g, c * scale) for g, c in results]
    return results
