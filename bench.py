"""Benchmark: the BASELINE north star's two headline workloads on one chip.

Leg definitions are FROZEN in `bench_manifest.json` (version field bumps
on any change, with the old->new delta explained in the leg's note) so
round-over-round numbers stay comparable.

Leg 1 — BERT-base trained from REAL token ids (embedding lookup ->
encoder -> loss), bf16, samples/sec/chip.
Leg 2 — ResNet-50 (the torch.fx-imported bottleneck tower of
examples/python/pytorch/resnet50_search.py, BASELINE.json configs[1]),
bf16, compiled under the auto-searched strategy, internal NHWC layout.
Leg 3 — BERT-base at seq 2048: the long-context path.

Prints ONE JSON line; `legs` carries all workloads' numbers.
vs_baseline anchors to A100-NCCL per-GPU throughput (the reference repo
publishes no absolute numbers, BASELINE.md:3-5).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# CPU smoke runs need a multi-device host for the tensor-parallel
# serving leg (tp=2 replica mesh); mirror tests/conftest.py's virtual
# 8-CPU topology.  Must land before jax initializes, and never touches
# the TPU path.
if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

_HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(_HERE, "bench_manifest.json")) as f:
    MANIFEST = json.load(f)
ANCHORS = MANIFEST["anchors"]


def _steady_state(ff, inputs, labels, iters, windows=None):
    """Best-of-N windows of `iters` serial steps, ONE hard sync each.

    The batch is device-resident and each step consumes the previous
    step's donated weights, so the chain is serial on-device; fetching
    the final loss drains it.  Window sizes are set in the manifest so
    the single host sync is a negligible share of the window
    (manifest.timing.history records what the old 10-step/2-sync
    windows cost r01/r02)."""
    windows = windows or MANIFEST["timing"]["windows"]

    def window(n):
        t0 = time.perf_counter()
        for _ in range(n):
            m = ff.train_step(inputs, labels)
        _ = float(m["loss"])  # one hard sync: drains the serial chain
        return time.perf_counter() - t0

    best = min(window(iters) for _ in range(windows))
    return best / iters  # seconds per step


def _build_bert_leg(dev, on_tpu, leg):
    import jax

    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models.transformer import build_bert

    if on_tpu:
        batch, seq = leg["batch"], leg["seq"]
        hidden, layers = leg["hidden"], leg["layers"]
        heads, inter = leg["heads"], leg["intermediate"]
        iters = leg["iters"]
    else:
        batch, seq, hidden, layers, heads, inter, iters = 8, 32, 64, 2, 4, 128, 3

    cfg = FFConfig(batch_size=batch, num_devices=1,
                   compute_dtype=leg["dtype"] if on_tpu else "float32")
    ff = FFModel(cfg)
    build_bert(ff, batch_size=batch, seq_length=seq, hidden_size=hidden,
               num_layers=layers, num_heads=heads, intermediate_size=inter,
               from_token_ids=True)
    ff.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        devices=[dev],
    )
    rng = np.random.RandomState(0)
    ids = jax.device_put(
        rng.randint(0, 30522, size=(batch, seq)).astype(np.int32),
        ff.executor.input_shardings()["input"],
    )
    y = jax.device_put(rng.randint(0, 2, batch).astype(np.int32),
                       ff.executor.label_sharding())
    for _ in range(3):
        m = ff.train_step({"input": ids}, y)
    _ = float(m["loss"])
    dt = _steady_state(ff, {"input": ids}, y, iters)
    return ff, batch, seq, dt


def bench_bert(dev, on_tpu):
    leg = MANIFEST["legs"]["bert_base"]
    print("bench[bert]: compiling", file=sys.stderr)
    ff, batch, seq, dt = _build_bert_leg(dev, on_tpu, leg)
    sps = batch / dt
    out = {
        "workload": f"BERT-base seq{seq} b{batch} token-ids train, bf16",
        "samples_per_sec_per_chip": round(sps, 2),
        "vs_a100": round(
            sps / ANCHORS["a100_bert_base_seq128_samples_per_sec"], 4
        ),
    }
    if on_tpu:
        out["mfu"] = _mfu(ff, dt)
        out.update(_fidelity(ff, dev, dt, "bert", leg))
    return out


def _mfu(ff, dt):
    """Model FLOPs utilization against the bench chip's bf16 roofline
    peak (sim/machine_model.py detect_device_spec).  Forward FLOPs come
    from the ops' own cost hooks; training charges backward at 2x
    forward (the standard dL/dx + dL/dw accounting — embedding scatter
    and elementwise ops count their own hooks).  VERDICT r03 Missing #4:
    vs_a100 alone flattered soft anchors; MFU is anchor-free."""
    try:
        from flexflow_tpu.sim.machine_model import detect_device_spec

        spec = detect_device_spec()
        fwd = sum(op.flops() for op in ff.operators.compute_ops())
        return round(3.0 * fwd / (dt * spec.peak_flops), 4)
    except Exception:  # pragma: no cover - diagnostics only
        return None


def _fidelity(ff, dev, dt, tag, leg=None):
    """Simulator fidelity vs the measured step: segment-granularity
    calibration (profiler.measure_segment_costs times the executor's own
    fused segment bodies — the r02 per-op harness was blind to XLA
    fusion and predicted 0.45x..3.6x).  The ratio is reported, not
    hidden (reference validates measure_operator_cost the same way).
    Per-leg `calibration` overrides in the manifest take precedence
    (v5: the bert leg needs finer binning than the global default)."""
    try:
        from flexflow_tpu.profiler import measure_segment_costs
        from flexflow_tpu.sim.machine_model import (
            TpuPodModel,
            detect_device_spec,
        )
        from flexflow_tpu.sim.simulator import OpCostModel, Simulator

        machine = TpuPodModel(topology=(1,), device=detect_device_spec())
        calib = dict(MANIFEST.get("calibration", {}))
        calib.update((leg or {}).get("calibration", {}))
        seg_costs = measure_segment_costs(
            ff, device=dev,
            max_regions=calib.get("max_regions", 16),
            repeats=calib.get("repeats", 3),
            chain=calib.get("chain", 48),
        )
        covered = sum(len(g) for g, _ in seg_costs)
        res = Simulator(machine, OpCostModel(machine)).simulate(
            ff.operators, {"data": 1}, training=True,
            segment_costs=seg_costs,
        )
        actual_ms = dt * 1e3
        out = {
            "predicted_step_ms": round(res.total_time * 1e3, 2),
            "actual_step_ms": round(actual_ms, 2),
            "predicted_vs_actual": round(res.total_time * 1e3 / actual_ms, 3),
            "calibration": f"{len(seg_costs)} regions / {covered} ops measured",
        }
        # unified fidelity record (obs/fidelity.py, manifest v8): the
        # same schema fit-time telemetry emits, so bench captures and
        # run_telemetry.jsonl records are directly comparable.  Built
        # from the SAME SimResult as the predicted_* fields above (no
        # second simulation, no disagreeing numbers).
        try:
            from flexflow_tpu.obs.fidelity import fidelity_record

            out["fidelity_record"] = fidelity_record(
                ff, dt, steps_measured=(leg or {}).get("iters", 0),
                source=f"bench/{tag}", segment_costs=seg_costs,
                sim_result=res,
            )
        except Exception as e:
            print(f"bench[{tag}]: fidelity record failed: {e}",
                  file=sys.stderr)
        return out
    except Exception as e:  # pragma: no cover - diagnostics only
        print(f"bench[{tag}]: prediction check failed: {e}", file=sys.stderr)
        return {}


def bench_bert_long(dev, on_tpu):
    leg = MANIFEST["legs"]["bert_long_context"]
    print("bench[bert-long]: compiling", file=sys.stderr)
    ff, batch, seq, dt = _build_bert_leg(dev, on_tpu, leg)
    dtype = "bf16" if on_tpu else "f32"
    out = {
        "workload": f"BERT-base seq{seq} b{batch} long-context train, {dtype}",
        "samples_per_sec_per_chip": round(batch / dt, 2),
        "tokens_per_sec_per_chip": round(batch * seq / dt, 0),
    }
    if on_tpu:
        out["mfu"] = _mfu(ff, dt)
    return out


def bench_resnet50(dev, on_tpu):
    import jax

    sys.path.insert(0, os.path.join(_HERE, "examples", "python", "pytorch"))
    from resnet50_search import ResNet50

    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.torch_frontend.model import PyTorchModel

    leg = MANIFEST["legs"]["resnet50"]
    if on_tpu:
        batch, px, classes, iters = (
            leg["batch"], leg["px"], leg["classes"], leg["iters"]
        )
    else:
        batch, px, classes, iters = 4, 32, 10, 3

    # auto-searched strategy per BASELINE.json configs[1] (single chip:
    # the search degenerates to the trivial mesh but the path runs;
    # calibration off keeps the bench inside its time box)
    cfg = FFConfig(batch_size=batch, num_devices=1,
                   search_budget=leg["search_budget"],
                   search_algo=leg["search_algo"], search_calibrate=False,
                   compute_dtype=leg["dtype"] if on_tpu else "float32")
    ff = FFModel(cfg)
    x = ff.create_tensor([batch, 3, px, px], name="input")
    pt = PyTorchModel(ResNet50(classes=classes))
    (out,) = pt.torch_to_ff(ff, [x])
    ff.softmax(out)
    ff.compile(
        optimizer=SGDOptimizer(lr=0.1),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        devices=[dev],
    )
    rng = np.random.RandomState(0)
    xs = jax.device_put(rng.randn(batch, 3, px, px).astype(np.float32),
                        ff.executor.input_shardings()["input"])
    ys = jax.device_put(rng.randint(0, classes, batch).astype(np.int32),
                        ff.executor.label_sharding())

    print("bench[resnet50]: compiled, warming up", file=sys.stderr)
    for _ in range(3):
        m = ff.train_step({"input": xs}, ys)
    _ = float(m["loss"])
    dt = _steady_state(ff, {"input": xs}, ys, iters)
    sps = batch / dt
    out = {
        "workload": f"ResNet-50 {px}px b{batch} fx-import train, bf16, "
                    f"searched strategy, NHWC internal layout",
        "samples_per_sec_per_chip": round(sps, 2),
        "vs_a100": round(sps / ANCHORS["a100_resnet50_samples_per_sec"], 4),
    }
    if on_tpu:
        out["mfu"] = _mfu(ff, dt)
        out.update(_fidelity(ff, dev, dt, "resnet50", leg))
    return out


def bench_dlrm(dev, on_tpu):
    """DLRM (BASELINE configs[3]): the attribute-parallel embedding
    workload — single-chip this measures the four 1M-row gather +
    grad-scatter paths plus the interaction MLPs (reference dlrm.cc
    prints THROUGHPUT the same way).  No A100 anchor exists for this
    exact config; the leg tracks round-over-round regressions."""
    import jax

    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models.dlrm import build_dlrm

    leg = MANIFEST["legs"]["dlrm"]
    if on_tpu:
        batch, tables, rows, iters = (
            leg["batch"], leg["tables"], leg["rows_per_table"], leg["iters"]
        )
    else:
        batch, tables, rows, iters = 16, 2, 1000, 3

    print("bench[dlrm]: compiling", file=sys.stderr)
    cfg = FFConfig(batch_size=batch, num_devices=1,
                   compute_dtype=leg["dtype"] if on_tpu else "float32")
    ff = FFModel(cfg)
    build_dlrm(ff, batch_size=batch, embedding_size=[rows] * tables,
               sparse_feature_size=leg["sparse_feature_size"],
               dense_feature_dim=leg["dense_feature_dim"],
               mlp_bot=leg["mlp_bot"], mlp_top=leg["mlp_top"])
    ff.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
        devices=[dev],
    )
    rng = np.random.RandomState(0)
    shardings = ff.executor.input_shardings()
    inputs = {
        f"sparse_input_{i}": jax.device_put(
            rng.randint(0, rows, size=(batch, 1)).astype(np.int32),
            shardings[f"sparse_input_{i}"])
        for i in range(tables)
    }
    inputs["dense_input"] = jax.device_put(
        rng.randn(batch, leg["dense_feature_dim"]).astype(np.float32),
        shardings["dense_input"])
    y = jax.device_put(
        rng.rand(batch, leg["mlp_top"][-1]).astype(np.float32),
        ff.executor.label_sharding())
    for _ in range(3):
        m = ff.train_step(inputs, y)
    _ = float(m["loss"])
    dt = _steady_state(ff, inputs, y, iters)
    out = {
        "workload": f"DLRM b{batch} {tables}x{rows}-row tables train "
                    f"(embedding gather/scatter path)",
        "samples_per_sec_per_chip": round(batch / dt, 2),
    }
    if on_tpu:
        out["mfu"] = _mfu(ff, dt)
        # The honest utilization denominator for this bandwidth-bound
        # leg is HBM traffic, not FLOPs (VERDICT r4 #6).  Dominant
        # per-step bytes, from the model config (f32 weights/grads):
        #   per table: dense-grad buffer write (jax.grad materializes
        #   the scatter-add into a table-sized f32 buffer) + SGD update
        #   read w + read g + write w  =  4 x table bytes;
        #   gather/scatter rows themselves are noise at b<<rows.
        d = leg["sparse_feature_size"]
        table_bytes = rows * d * 4
        step_bytes = tables * 4 * table_bytes
        from flexflow_tpu.sim.machine_model import detect_device_spec

        peak = detect_device_spec().hbm_bandwidth
        out["hbm_gb_per_step"] = round(step_bytes / 1e9, 3)
        out["achieved_hbm_gbps"] = round(step_bytes / dt / 1e9, 1)
        out["hbm_utilization"] = round(step_bytes / dt / peak, 4)
    return out


def bench_moe_dispatch(dev, on_tpu):
    """MoE dispatch microbench: sort-based group_by+combine (the Pallas-
    era TPU trick, ops/moe_dispatch.py) vs the one-hot-matmul dispatch
    it replaces (group_by.cu's scatter in dense form), fixed
    tokens x experts.  Reports microseconds per dispatch+combine and the
    speedup (VERDICT r03 Missing #3)."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops.moe_dispatch import sort_combine, sort_group_by

    leg = MANIFEST["legs"]["moe_dispatch"]
    if on_tpu:
        tokens, experts, k, d = (leg["tokens"], leg["experts"], leg["k"],
                                 leg["d_model"])
        iters, windows = leg["iters"], MANIFEST["timing"]["windows"]
    else:
        tokens, experts, k, d, iters, windows = 256, 8, 2, 64, 3, 1

    capacity = max(1, int(leg["capacity_factor"] * tokens * k // experts))
    rng = np.random.RandomState(0)
    data = jax.device_put(
        rng.randn(tokens, d).astype(np.float32), dev)
    assign = jax.device_put(
        rng.randint(0, experts, size=(tokens, k)).astype(np.int32), dev)

    def sort_rows(data, assign):
        grouped = sort_group_by(data, assign, experts, capacity)
        rows, keep = sort_combine(grouped, assign, capacity)
        return rows

    def onehot_rows(data, assign, precision=None):
        # dense dispatch: [tokens*k, experts*cap] one-hot matmul (what
        # sort-based dispatch replaces; reference group_by.cu scatter)
        flat = assign.reshape(-1)
        bk = flat.shape[0]
        # position-within-expert via cumsum over one-hot (dense ranks)
        oh = jax.nn.one_hot(flat, experts, dtype=data.dtype)  # [bk, n]
        rank = (jnp.cumsum(oh, axis=0) - oh) * oh  # rank per token
        r = jnp.sum(rank, axis=1).astype(jnp.int32)
        keep = r < capacity
        slot_oh = (oh[:, :, None]
                   * jax.nn.one_hot(jnp.minimum(r, capacity - 1), capacity,
                                    dtype=data.dtype)[:, None, :])
        slot_oh = slot_oh.reshape(bk, experts * capacity)
        slot_oh = slot_oh * keep[:, None].astype(data.dtype)
        rows = jnp.repeat(data, k, axis=0)
        grouped = jnp.matmul(slot_oh.T, rows, precision=precision)  # [n*cap, d]
        back = jnp.matmul(slot_oh, grouped, precision=precision)  # combine
        return back

    sort_path = jax.jit(lambda d, a: jnp.sum(sort_rows(d, a)))
    onehot_path = jax.jit(lambda d, a: jnp.sum(onehot_rows(d, a)))

    # both paths implement the same capacity-bounded dispatch: each
    # (expert, slot) receives exactly one token row, so at exact matmul
    # precision the full row arrays must agree (TPU's default-precision
    # matmul truncates f32 operands to bf16 passes, which is why the
    # value check pins precision while the TIMED one-hot path keeps the
    # default — the realistic, faster dense dispatch); recorded in the
    # JSON so a silent divergence can't masquerade as a speedup
    match_fn = jax.jit(lambda d, a: jnp.all(jnp.isclose(
        sort_rows(d, a),
        onehot_rows(d, a, precision=jax.lax.Precision.HIGHEST),
        rtol=1e-4, atol=1e-5)))  # on-device: one boolean comes back to the host
    paths_match = bool(match_fn(data, assign))

    def time_fn(fn):
        _ = float(fn(data, assign))  # compile + warm

        def window():
            t0 = time.perf_counter()
            for _ in range(iters):
                r = fn(data, assign)
            _ = float(r)
            return (time.perf_counter() - t0) / iters

        return min(window() for _ in range(windows))

    t_sort = time_fn(sort_path)
    t_onehot = time_fn(onehot_path)
    return {
        "workload": f"MoE dispatch+combine {tokens} tok x {experts} experts "
                    f"k={k} cap_factor={leg['capacity_factor']}",
        "sort_dispatch_us": round(t_sort * 1e6, 1),
        "one_hot_dispatch_us": round(t_onehot * 1e6, 1),
        "sort_vs_one_hot_speedup": round(t_onehot / t_sort, 2),
        "paths_match": paths_match,
    }


def bench_weight_update(on_tpu):
    """ZeRO-1 weight-update microbench (manifest v7): the Adam update
    pass over the BERT-base parameter set, sharded along a dp mesh of
    all visible devices vs replicated.  Uses the executor's own spec
    machinery (parallel/zero.py) so a regression in the update path —
    compute or layout — moves these numbers.  update-ms is a serial
    chain of donated updates with one hard sync."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from flexflow_tpu.optimizer import AdamOptimizer
    from flexflow_tpu.parallel.zero import shard_update_sharding

    leg = MANIFEST["legs"]["weight_update"]
    if on_tpu:
        hidden, layers = leg["hidden"], leg["layers"]
        inter, vocab, iters = leg["intermediate"], leg["vocab"], leg["iters"]
    else:
        hidden, layers, inter, vocab, iters = 64, 2, 128, 1000, 3

    devs = jax.devices()
    dp = len(devs)
    mesh = Mesh(np.asarray(devs), ("data",))
    rep = NamedSharding(mesh, PartitionSpec())

    shapes = {"embed.weight": (vocab, hidden)}
    for i in range(layers):
        shapes.update({
            f"l{i}.qkv": (hidden, 3 * hidden),
            f"l{i}.proj": (hidden, hidden),
            f"l{i}.up": (hidden, inter),
            f"l{i}.down": (inter, hidden),
            f"l{i}.ln_scale": (hidden,),
            f"l{i}.ln_bias": (hidden,),
        })
    rng = np.random.RandomState(0)
    host_w = {k: rng.randn(*s).astype(np.float32) * 0.02
              for k, s in shapes.items()}
    host_g = {k: rng.randn(*s).astype(np.float32) * 1e-3
              for k, s in shapes.items()}
    opt = AdamOptimizer(alpha=1e-3)
    out = {
        "workload": f"Adam update, BERT-base param set "
                    f"({layers}L h{hidden}), dp={dp} "
                    f"(ZeRO-1 sharded vs replicated)",
        "dp": dp,
    }
    for mode in ("replicated", "sharded"):
        slot_sh = {
            k: (shard_update_sharding(rep, v.shape, mesh, "data")
                if mode == "sharded" else rep)
            for k, v in host_w.items()
        }
        weights = {k: jax.device_put(v, rep) for k, v in host_w.items()}
        grads = {k: jax.device_put(v, rep) for k, v in host_g.items()}
        state = opt.init_state(weights)
        state = {
            k: (jax.tree.map(lambda v, s: jax.device_put(v, s), sub, slot_sh)
                if isinstance(sub, dict) else jax.device_put(sub, rep))
            for k, sub in state.items()
        }

        def step(w, s, g, _sh=slot_sh, _mode=mode):
            if _mode == "sharded":
                g = jax.tree.map(jax.lax.with_sharding_constraint, g, _sh)
                w = jax.tree.map(jax.lax.with_sharding_constraint, w, _sh)
            nw, ns = opt.update(w, g, s)
            if _mode == "sharded":
                nw = jax.tree.map(
                    lambda v: jax.lax.with_sharding_constraint(v, rep), nw
                )
                ns = {
                    k: (jax.tree.map(
                        jax.lax.with_sharding_constraint, sub, _sh)
                        if isinstance(sub, dict) else sub)
                    for k, sub in ns.items()
                }
            return nw, ns

        jstep = jax.jit(step, donate_argnums=(0, 1))
        weights, state = jstep(weights, state, grads)  # compile + warm
        jax.block_until_ready(jax.tree.leaves(weights)[0])

        def window():
            nonlocal weights, state
            t0 = time.perf_counter()
            for _ in range(iters):
                weights, state = jstep(weights, state, grads)
            jax.block_until_ready(jax.tree.leaves(weights)[0])
            return (time.perf_counter() - t0) / iters

        dt = min(window() for _ in range(MANIFEST["timing"]["windows"]))
        slot_bytes = sum(
            int(np.prod(sub[k2].sharding.shard_shape(sub[k2].shape))
                * sub[k2].dtype.itemsize)
            for key, sub in state.items() if isinstance(sub, dict)
            for k2 in sub
        )
        out[f"update_ms_{mode}"] = round(dt * 1e3, 3)
        out[f"opt_state_mb_per_device_{mode}"] = round(
            slot_bytes / 2**20, 2
        )
    if out["update_ms_sharded"] > 0:
        out["sharded_vs_replicated_speedup"] = round(
            out["update_ms_replicated"] / out["update_ms_sharded"], 2
        )
    return out


def bench_zero_ladder(dev, on_tpu):
    """ZeRO-ladder leg (manifest v14): stages 0-3 through the REAL
    executor — per stage, the same dp-mesh Adam MLP is compiled with
    --zero-stage and the leg times GraphExecutor's wrapped update pass
    (the exact reduce-scatter / 1-over-dp-shard update / all-gather
    wiring fit runs) and records grad-buffer, master-weight-resident,
    and opt-state bytes/device from the actual NamedShardings.  On
    dp=1 every stage coincides (update-pass regression tracker); on
    multi-device captures grad bytes fall ~1/dp at stage >= 2 and
    weight-resident bytes at stage 3."""
    import jax

    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.fftype import ActiMode
    from flexflow_tpu.optimizer import AdamOptimizer
    from flexflow_tpu.strategy import data_parallel_strategy

    leg = MANIFEST["legs"]["zero_ladder"]
    if on_tpu:
        in_dim, hidden, layers = leg["input_dim"], leg["hidden"], leg["layers"]
        classes, batch, iters = leg["classes"], leg["batch"], leg["iters"]
    else:
        in_dim, hidden, layers, classes, batch, iters = 128, 256, 2, 64, 8, 3

    devs = jax.devices()
    dp = len(devs)
    out = {
        "workload": f"Adam MLP {layers}L h{hidden}, dp={dp}, "
                    f"executor update pass at --zero-stage 0..3",
        "dp": dp,
        "stages": {},
    }

    def tree_mb(shardings, leaves):
        """Per-device MB of `leaves` laid out per the sharding tree."""
        b = 0
        for op_name, entry in shardings.items():
            for wname, sh in entry.items():
                leaf = leaves[op_name][wname]
                b += int(np.prod(sh.shard_shape(leaf.shape))
                         * leaf.dtype.itemsize)
            # noqa: E501 — exact shard-shape sums, no estimate
        return round(b / 2**20, 3)

    for stage in (0, 1, 2, 3):
        cfg = FFConfig(batch_size=batch, num_devices=dp, zero_stage=stage)
        ff = FFModel(cfg)
        x = ff.create_tensor([batch, in_dim], name="x")
        t = x
        for _ in range(layers):
            t = ff.dense(t, hidden, activation=ActiMode.RELU)
        t = ff.dense(t, classes)
        ff.softmax(t)
        ff.compile(
            optimizer=AdamOptimizer(alpha=1e-3),
            loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
            strategy=data_parallel_strategy(dp),
            devices=devs,
        )
        ex = ff.executor
        grad_sh = ex.grad_shardings()
        grads = jax.tree.map(
            lambda v, s: jax.device_put(np.asarray(v) * 1e-3, s),
            ff._weights, grad_sh,
        )
        update_fn = ex._make_update_fn(ff.optimizer)
        jstep = jax.jit(update_fn, donate_argnums=(0, 2))
        weights, state = jstep(ff._weights, grads, ff._opt_state)
        jax.block_until_ready(jax.tree.leaves(weights)[0])

        def window():
            nonlocal weights, state
            t0 = time.perf_counter()
            for _ in range(iters):
                weights, state = jstep(weights, grads, state)
            jax.block_until_ready(jax.tree.leaves(weights)[0])
            return (time.perf_counter() - t0) / iters

        dt = min(window() for _ in range(MANIFEST["timing"]["windows"]))
        slot_b = sum(
            int(np.prod(leaf.sharding.shard_shape(leaf.shape))
                * leaf.dtype.itemsize)
            for sub in state.values() if isinstance(sub, dict)
            for entry in sub.values() for leaf in entry.values()
        )
        out["stages"][f"zero{stage}"] = {
            "update_ms": round(dt * 1e3, 3),
            "grad_mb_per_device": tree_mb(grad_sh, ff._weights),
            "weight_resident_mb_per_device": tree_mb(
                ex.master_weight_shardings(), ff._weights
            ),
            "opt_state_mb_per_device": round(slot_b / 2**20, 3),
            "fallback_leaves": len(ex.zero_fallback_leaves()),
        }
    s1 = out["stages"]["zero1"]
    s2, s3 = out["stages"]["zero2"], out["stages"]["zero3"]
    if s1["grad_mb_per_device"] > 0:
        out["grad_shrink_stage2"] = round(
            s1["grad_mb_per_device"] / max(s2["grad_mb_per_device"], 1e-9), 2
        )
    if s1["weight_resident_mb_per_device"] > 0:
        out["weight_shrink_stage3"] = round(
            s1["weight_resident_mb_per_device"]
            / max(s3["weight_resident_mb_per_device"], 1e-9), 2
        )
    return out


def bench_long_context(dev, on_tpu):
    """Searched-remat long-context leg (manifest v17, docs/PERF.md
    "Searched rematerialization"): the seq2048 BERT config under
    --memory-search with a modeled per-device HBM budget sized strictly
    between the all-on-remat and no-remat footprints.  The no-remat
    ladder cannot fit (OOM at the modeled ceiling); the search must
    choose a per-segment remat plan that does, at less simulated time
    than checkpointing everything.  The chosen plan is then LOWERED
    through the real executor (jax.checkpoint on exactly the chosen
    segments) and the leg logs predicted-vs-measured step time for it."""
    import dataclasses as _dc

    import jax

    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models.transformer import build_bert
    from flexflow_tpu.pcg.evaluator import IncrementalEvaluator
    from flexflow_tpu.pcg.unity import UnitySearch
    from flexflow_tpu.sim.machine_model import (
        TpuPodModel,
        detect_device_spec,
    )
    from flexflow_tpu.sim.simulator import (
        OpCostModel,
        Simulator,
        remat_segments,
    )
    from flexflow_tpu.strategy import data_parallel_strategy

    leg = MANIFEST["legs"]["long_context"]
    if on_tpu:
        batch, seq = leg["batch"], leg["seq"]
        hidden, layers = leg["hidden"], leg["layers"]
        heads, inter = leg["heads"], leg["intermediate"]
        iters, vocab = leg["iters"], 30522
    else:
        # smoke dims stay activation-dominated (small vocab/hidden,
        # larger batch x seq) so the remat decision is still exercised
        batch, seq, hidden, layers, heads, inter, iters = 32, 128, 64, 2, 4, 128, 3
        vocab = 512

    print("bench[long-context]: searching remat plan", file=sys.stderr)
    cfg = FFConfig(batch_size=batch, num_devices=1,
                   compute_dtype=leg["dtype"] if on_tpu else "float32")
    ff = FFModel(cfg)
    build_bert(ff, batch_size=batch, seq_length=seq, hidden_size=hidden,
               num_layers=layers, num_heads=heads, intermediate_size=inter,
               vocab_size=vocab, from_token_ids=True)
    machine = TpuPodModel(topology=(1,), device=detect_device_spec())
    sim = Simulator(machine)
    ev = IncrementalEvaluator(ff.layers, sim)
    dp = data_parallel_strategy(1)
    dense = ev.evaluate(dp)
    n_seg = len(remat_segments(dense.ops))
    all_on = ev.evaluate(_dc.replace(dp, remat=list(range(n_seg))))
    saved = dense.per_device_memory - all_on.per_device_memory
    budget = all_on.per_device_memory + int(saved * leg["budget_frac"])

    search = UnitySearch(ff.layers, 1, machine, OpCostModel(machine),
                         memory_budget=budget, enable_pipeline=False,
                         remat_search=True, budget=leg["search_budget"])
    chosen = search.optimize_with_memory()
    plan = list(chosen.remat or []) if chosen is not None else []
    res = ev.evaluate(chosen) if chosen is not None else dense
    out = {
        "workload": f"BERT-base seq{seq} b{batch} --memory-search with "
                    f"per-segment remat, modeled HBM budget between the "
                    f"all-on and no-remat footprints",
        "segments": n_seg,
        "remat_plan": ",".join(str(i) for i in plan),
        "remat_segments_on": len(plan),
        "modeled_budget_mb": round(budget / 2**20, 1),
        "no_remat_mb": round(dense.per_device_memory / 2**20, 1),
        "all_on_mb": round(all_on.per_device_memory / 2**20, 1),
        "chosen_mb": round(res.per_device_memory / 2**20, 1),
        # the acceptance triple: the dense ladder OOMs the modeled
        # ceiling, the chosen plan fits it, and costs less simulated
        # time than checkpointing everything
        "no_remat_fits_budget": bool(dense.per_device_memory <= budget),
        "chosen_fits_budget": bool(res.per_device_memory <= budget),
        "predicted_step_ms_no_remat": round(dense.total_time * 1e3, 3),
        "predicted_step_ms_all_on": round(all_on.total_time * 1e3, 3),
        "predicted_step_ms_chosen": round(res.total_time * 1e3, 3),
        "chosen_beats_all_on": bool(res.total_time < all_on.total_time),
        "predicted_recompute_ms": round(res.recompute_s * 1e3, 3),
        "remat_nontrivial": bool(
            plan and len(plan) < sum(
                1 for _, pure in remat_segments(dense.ops) if pure
            )
        ),
        "saved_activation_mb": round(
            (dense.activation_bytes - res.activation_bytes) / 2**20, 2
        ),
    }
    # the acceptance bar, asserted like the other legs' (a silent
    # search regression must fail the capture, not footnote it)
    assert not out["no_remat_fits_budget"]
    assert out["chosen_fits_budget"], out
    assert out["chosen_beats_all_on"], out

    # lower the chosen plan through the real executor and measure
    print("bench[long-context]: compiling chosen plan", file=sys.stderr)
    ff.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        strategy=chosen if chosen is not None else dp,
        devices=[dev],
    )
    rng = np.random.RandomState(0)
    ids = jax.device_put(
        rng.randint(0, vocab, size=(batch, seq)).astype(np.int32),
        ff.executor.input_shardings()["input"],
    )
    y = jax.device_put(rng.randint(0, 2, batch).astype(np.int32),
                       ff.executor.label_sharding())
    for _ in range(3):
        m = ff.train_step({"input": ids}, y)
    _ = float(m["loss"])
    dt = _steady_state(ff, {"input": ids}, y, iters)
    out["measured_step_ms"] = round(dt * 1e3, 3)
    out["predicted_vs_measured"] = round(
        res.total_time / dt, 3
    ) if dt > 0 else None
    out["tokens_per_sec_per_chip"] = round(batch * seq / dt, 0)
    ex_plan = ff.executor._remat_plan
    out["executor_segments_checkpointed"] = (
        sum(1 for *_, pure in ex_plan if pure) if ex_plan else 0
    )
    return out


def bench_multi_slice(dev, on_tpu):
    """Multi-slice topology leg (manifest v16, docs/TOPOLOGY.md): the
    same model searched on a flat 1x8 mesh vs a 2x4 slice hierarchy
    with a simulated DCN ~20x slower than the effective ICI.  Reports
    the predicted step time on each machine, the searched placement
    (which mesh axis crosses the DCN boundary), whether the grad
    reduction lowers hierarchically, and the per-tier predicted comm
    bytes — asserting the searched strategy keeps the bulk of its
    traffic intra-slice (dcn_bytes < ici_bytes)."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.fftype import ActiMode
    from flexflow_tpu.pcg.evaluator import IncrementalEvaluator
    from flexflow_tpu.pcg.unity import UnitySearch
    from flexflow_tpu.sim.machine_model import TpuPodModel
    from flexflow_tpu.sim.simulator import OpCostModel, Simulator
    from flexflow_tpu.topology.hierarchy import SliceHierarchy

    leg = MANIFEST["legs"]["multi_slice"]
    batch, hidden = leg["batch"], leg["hidden"]
    slices, dcn_bw = leg["slices"], leg["dcn_bandwidth"]
    n = leg["devices"]
    per_slice = n // slices
    print("bench[multi_slice]: searching flat vs hierarchy",
          file=sys.stderr)

    ff = FFModel(FFConfig(batch_size=batch))
    x = ff.create_tensor([batch, hidden], name="x")
    t = ff.dense(x, hidden, activation=ActiMode.RELU)
    t = ff.dense(t, hidden, activation=ActiMode.RELU)
    t = ff.dense(t, 8)
    ff.softmax(t)

    out = {
        "workload": f"{slices}x{per_slice} hierarchy vs 1x{n} flat, "
                    f"MLP b{batch} h{hidden}, unity search "
                    f"(simulator-driven; DCN {dcn_bw / 1e9:g} GB/s)",
        "machines": {},
    }
    machines = {
        "flat_1x8": TpuPodModel(topology=(n,)),
        f"hier_{slices}x{per_slice}": SliceHierarchy(
            topology=(per_slice,), slices=slices,
            dcn_bw_per_host=dcn_bw, dcn_latency=leg["dcn_latency"],
        ),
    }
    for name, machine in machines.items():
        search = UnitySearch(ff.layers, n, machine, OpCostModel(machine),
                             enable_pipeline=False)
        best = search.optimize()
        res = IncrementalEvaluator(ff.layers, Simulator(machine)).evaluate(
            best
        )
        tiers = res.comm_tiers
        entry = {
            "mesh_axes": dict(best.mesh_axes),
            "predicted_step_ms": round(res.total_time * 1e3, 4),
            "placement": best.search_stats["placement"],
            "hierarchical_reduction":
                best.search_stats["hierarchical_reduction"],
            "ici_comm_kb": round(tiers["ici_bytes"] / 1024.0, 2),
            "dcn_comm_kb": round(tiers["dcn_bytes"] / 1024.0, 2),
        }
        out["machines"][name] = entry
    hier = out["machines"][f"hier_{slices}x{per_slice}"]
    flat = out["machines"]["flat_1x8"]
    # the hierarchy-searched winner keeps the bulk of its comm on ICI
    out["dp_traffic_intra_slice"] = bool(
        hier["dcn_comm_kb"] < hier["ici_comm_kb"]
    )
    assert out["dp_traffic_intra_slice"], (
        "hierarchy search left more predicted bytes on DCN than ICI: "
        f"{hier}"
    )
    out["hier_vs_flat_predicted"] = round(
        hier["predicted_step_ms"] / max(flat["predicted_step_ms"], 1e-9), 3
    )
    return out


def _fsck_verdict(local_dir=None, remote_uri=None):
    """Post-bench verification (manifest v15): run the offline
    two-tier checkpoint verifier (tools/checkpoint_fsck.py) over the
    dirs a leg just produced, BEFORE they are cleaned up — a bench
    that published a corrupt checkpoint should say so in its own
    numbers, not pass silently."""
    from tools.checkpoint_fsck import fsck_local, fsck_remote

    out = {}
    problems = []
    if local_dir is not None:
        rep = fsck_local(local_dir)
        step_problems = [p for s in rep["steps"].values()
                         for p in s["problems"]]
        problems += rep["problems"] + step_problems
        out["local_steps_verified"] = sum(
            1 for s in rep["steps"].values() if s["ok"])
    if remote_uri is not None:
        rep = fsck_remote(remote_uri)
        step_problems = [p for s in rep.get("steps", {}).values()
                         for p in s["problems"]]
        problems += rep.get("problems", []) + step_problems
        out["remote_steps_verified"] = sum(
            1 for s in rep.get("steps", {}).values() if s["ok"])
    out["ok"] = not problems
    if problems:
        out["problems"] = problems[:5]
    return out


def bench_checkpoint(dev, on_tpu):
    """Checkpoint-stall microbench (manifest v9): the step-boundary
    stall of a full-train-state save under the durability layer
    (checkpoint.py).  Sync saves pay serialize + fsync + crc-verify +
    publish inline; async saves (`wait=False`) stall only for the
    device->host snapshot and hand the rest to the background writer —
    this leg records both stalls plus the writer's flush throughput, so
    a regression in either the snapshot path or the verified-write path
    moves a number."""
    import shutil
    import tempfile

    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.checkpoint import LocalCheckpointManager
    from flexflow_tpu.fftype import ActiMode
    from flexflow_tpu.optimizer import AdamOptimizer

    leg = MANIFEST["legs"]["checkpoint"]
    if on_tpu:
        in_dim, hidden, layers = leg["input_dim"], leg["hidden"], leg["layers"]
        classes, batch, iters = leg["classes"], leg["batch"], leg["iters"]
    else:
        in_dim, hidden, layers, classes, batch, iters = 256, 512, 3, 512, 16, 3

    cfg = FFConfig(batch_size=batch, num_devices=1)
    ff = FFModel(cfg)
    t = ff.create_tensor([batch, in_dim], name="x")
    for _ in range(layers):
        t = ff.dense(t, hidden, activation=ActiMode.RELU)
    t = ff.dense(t, classes)
    ff.softmax(t)
    # Adam: m/v slots triple the serialized state vs bare weights —
    # the realistic full-train-state payload
    ff.compile(optimizer=AdamOptimizer(alpha=1e-3),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=[dev])
    rng = np.random.RandomState(0)
    xs = rng.randn(batch, in_dim).astype(np.float32)
    ys = rng.randint(0, classes, size=batch).astype(np.int32)
    m = ff.train_step({"x": xs}, ys)  # materialize weights + slots
    _ = float(m["loss"])

    tmpdir = tempfile.mkdtemp(prefix="ckpt_bench_")
    try:
        mgr = LocalCheckpointManager(tmpdir, max_to_keep=2)
        sync_stalls, async_stalls, flushes = [], [], []
        step = 0
        for _ in range(iters):
            step += 1
            t0 = time.perf_counter()
            mgr.save(ff, step, wait=True)
            sync_stalls.append(time.perf_counter() - t0)
        for _ in range(iters):
            step += 1
            t0 = time.perf_counter()
            mgr.save(ff, step, wait=False)
            t1 = time.perf_counter()
            async_stalls.append(t1 - t0)  # snapshot + enqueue only
            failures = mgr.drain()
            flushes.append(time.perf_counter() - t1)
            assert not failures, failures
        with open(os.path.join(mgr._path(step), "manifest.json")) as f:
            total_bytes = json.load(f)["total_bytes"]
        mgr.close()
        fsck = _fsck_verdict(local_dir=tmpdir)
        assert fsck["ok"], fsck
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)

    stall_sync = min(sync_stalls)
    stall_async = min(async_stalls)
    flush = min(flushes)
    return {
        "workload": f"full-train-state save ({layers}L h{hidden} Adam), "
                    "sync write vs async snapshot-only stall, crc32-verified",
        "state_mb": round(total_bytes / 2**20, 2),
        "stall_ms_sync": round(stall_sync * 1e3, 3),
        "stall_ms_async_snapshot": round(stall_async * 1e3, 3),
        "async_stall_below_sync": bool(stall_async < stall_sync),
        "sync_vs_async_stall_ratio": round(stall_sync / max(stall_async, 1e-9), 2),
        "flush_ms": round(flush * 1e3, 3),
        # serialize+fsync+verify+publish throughput of the background writer
        "write_mb_per_s": round(total_bytes / 2**20 / max(flush, 1e-9), 1),
        "fsck": fsck,
    }


def bench_cold_start(dev, on_tpu):
    """Cold-start leg (manifest v11): what the strategy store buys at
    process start.  Same model, same config, twice against one store
    root: the first `FFModel.compile` pays the Unity search and
    publishes; the second restores the strategy (search_stats records
    store_hit) — the leg reports both wall times and the speedup.
    When the host exposes >= 8 devices it also measures the resilience
    supervisor's elastic 8->4 device-loss recovery cold (re-search on
    the 4-survivor mesh) vs warm (the degraded-mesh key is already
    published), the store's second job after replica spin-up."""
    import shutil
    import tempfile

    import jax

    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.fftype import ActiMode
    from flexflow_tpu.optimizer import SGDOptimizer

    leg = MANIFEST["legs"]["cold_start"]
    hidden, layers = leg["hidden"], leg["layers"]
    classes, batch = leg["classes"], leg["batch"]
    budget = leg["search_budget"]

    devs = jax.devices()
    n = min(len(devs), leg["devices_cap"])

    def build(store_root, ndev, **cfg_kw):
        cfg = FFConfig(batch_size=batch, num_devices=ndev,
                       search_budget=budget, strategy_store=store_root,
                       enable_parameter_parallel=True, **cfg_kw)
        ff = FFModel(cfg)
        t = ff.create_tensor([batch, leg["input_dim"]], name="x")
        for _ in range(layers):
            t = ff.dense(t, hidden, activation=ActiMode.RELU)
        t = ff.dense(t, classes)
        ff.softmax(t)
        return ff

    def timed_compile(store_root):
        ff = build(store_root, n)
        t0 = time.perf_counter()
        ff.compile(optimizer=SGDOptimizer(lr=0.01),
                   loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                   devices=devs[:n])
        return time.perf_counter() - t0, ff

    tmpdir = tempfile.mkdtemp(prefix="cold_start_bench_")
    try:
        cold_s, ff_cold = timed_compile(tmpdir)
        warm_s, ff_warm = timed_compile(tmpdir)
        assert not ff_cold.strategy.search_stats.get("store_hit")
        assert ff_warm.strategy.search_stats.get("store_hit")
        result = {
            "workload": f"compile-with-search vs compile-with-warm-store "
                        f"({layers}L h{hidden} MLP, unity budget {budget}, "
                        f"{n} devices)",
            "compile_s_cold": round(cold_s, 3),
            "compile_s_warm": round(warm_s, 3),
            "warm_store_hit": True,
            "cold_vs_warm_speedup": round(cold_s / max(warm_s, 1e-9), 2),
        }
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)

    # -- elastic 8->4 recovery, warm vs cold store ----------------------
    result["elastic"] = None
    if len(devs) >= 8:
        from flexflow_tpu.resilience import FaultPlan
        from flexflow_tpu.resilience.faults import FaultKind

        steps, fault_step = leg["elastic_steps"], leg["elastic_fault_step"]
        rng = np.random.RandomState(0)
        xs = rng.randn(batch * 4, leg["input_dim"]).astype(np.float32)
        ys = rng.randint(0, classes, size=batch * 4).astype(np.int32)

        def run_once(store_root, ckpt_dir):
            ff = build(store_root, 8, checkpoint_every=1, max_restarts=3,
                       retry_backoff=0.0)
            ff.compile(optimizer=SGDOptimizer(lr=0.01),
                       loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                       devices=devs[:8])
            plan = FaultPlan.single(fault_step, FaultKind.DEVICE_LOSS,
                                    survivors=4)
            t0 = time.perf_counter()
            report = ff.fit_resilient(
                {"x": xs}, ys, num_steps=steps, batch_size=batch,
                directory=ckpt_dir, fault_plan=plan,
            )
            dt = time.perf_counter() - t0
            assert report.final_step == steps
            return dt, report.counters

        store2 = tempfile.mkdtemp(prefix="cold_start_elastic_")
        try:
            ck1 = tempfile.mkdtemp(prefix="cold_start_ck1_")
            ck2 = tempfile.mkdtemp(prefix="cold_start_ck2_")
            try:
                cold_run_s, cold_counters = run_once(store2, ck1)
                warm_run_s, warm_counters = run_once(store2, ck2)
                assert cold_counters["re_search_store_hits"] == 0
            finally:
                shutil.rmtree(ck1, ignore_errors=True)
                shutil.rmtree(ck2, ignore_errors=True)
            result["elastic"] = {
                "recovery_run_s_cold": round(cold_run_s, 3),
                "recovery_run_s_warm": round(warm_run_s, 3),
                "warm_re_search_store_hits": int(
                    warm_counters["re_search_store_hits"]
                ),
                "cold_vs_warm_speedup": round(
                    cold_run_s / max(warm_run_s, 1e-9), 2
                ),
            }
        finally:
            shutil.rmtree(store2, ignore_errors=True)
    return result


def bench_host_loss(dev, on_tpu):
    """Host-loss leg (manifest v13): what the durable offload tier
    costs in steady state and what it buys after a full host loss.

    Block 1 — steady-state overhead: the same supervised training run
    with the checkpoint mirror OFF vs ON (filesystem blob backend);
    the mirror uploads on a background thread, so the per-step delta
    should be noise.

    Block 2 — fresh-host recovery: after the offload-ON run, the
    entire local checkpoint directory AND strategy store are deleted
    (the host loss).  Time-to-first-step on a brand-new "host":
    compile (warm REMOTE strategy store — the search is skipped) +
    restore from REMOTE_LATEST + one training step, vs a fully cold
    start (fresh search, no checkpoint, training from step 0)."""
    import shutil
    import tempfile

    import jax

    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.fftype import ActiMode
    from flexflow_tpu.optimizer import SGDOptimizer

    leg = MANIFEST["legs"]["host_loss"]
    hidden, layers = leg["hidden"], leg["layers"]
    classes, batch = leg["classes"], leg["batch"]
    steps, every = leg["steps"], leg["checkpoint_every"]

    devs = jax.devices()
    n = min(len(devs), 8)
    rng = np.random.RandomState(0)
    xs = rng.randn(batch * 4, leg["input_dim"]).astype(np.float32)
    ys = rng.randint(0, classes, size=batch * 4).astype(np.int32)

    def build(store_root=None, remote=None, budget=0):
        cfg = FFConfig(batch_size=batch, num_devices=n,
                       search_budget=budget, strategy_store=store_root,
                       remote_store=remote, checkpoint_every=every,
                       enable_parameter_parallel=bool(budget),
                       retry_backoff=0.0)
        ff = FFModel(cfg)
        t = ff.create_tensor([batch, leg["input_dim"]], name="x")
        for _ in range(layers):
            t = ff.dense(t, hidden, activation=ActiMode.RELU)
        t = ff.dense(t, classes)
        ff.softmax(t)
        ff.compile(optimizer=SGDOptimizer(lr=0.01),
                   loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                   devices=devs[:n])
        return ff

    def run_steps(ff, ckpt_dir, num_steps, resume=False):
        t0 = time.perf_counter()
        report = ff.fit_resilient({"x": xs}, ys, num_steps=num_steps,
                                  batch_size=batch, directory=ckpt_dir,
                                  resume=resume)
        return time.perf_counter() - t0, report

    roots = {name: tempfile.mkdtemp(prefix=f"host_loss_{name}_")
             for name in ("ck_off", "ck_on", "blob", "store", "store2",
                          "ck_fresh", "ck_cold")}
    try:
        # -- block 1: steady-state step-time overhead, offload off/on --
        # both runs share the strategy store, so the OFF baseline
        # executes the SAME searched strategy (warm hit) and the delta
        # isolates the mirror, not a strategy difference.  The ON model
        # builds FIRST: its fresh search publishes through to the fleet
        # mirror (a warm local hit would not), which block 2 relies on
        ff_on = build(store_root=roots["store"], remote=roots["blob"],
                      budget=leg["search_budget"])
        ff_off = build(store_root=roots["store"],
                       budget=leg["search_budget"])
        assert ff_on.strategy.to_json() == ff_off.strategy.to_json()
        # identical 2-step warmup each, so neither timed run pays the
        # process's one-time XLA/first-touch costs
        for ff in (ff_off, ff_on):
            for _ in range(2):
                ff.train_step({"x": xs[:batch]}, ys[:batch])
        off_s, off_rep = run_steps(ff_off, roots["ck_off"], steps)
        on_s, on_rep = run_steps(ff_on, roots["ck_on"], steps)
        assert off_rep.final_step == steps and on_rep.final_step == steps
        assert on_rep.counters["offload_uploads"] >= 1
        step_ms_off = off_s / steps * 1e3
        step_ms_on = on_s / steps * 1e3
        del ff_off, ff_on

        # -- block 2: the host dies — local ckpts + store are GONE ----
        shutil.rmtree(roots["ck_on"])
        shutil.rmtree(roots["store"])

        t0 = time.perf_counter()
        ff_warm = build(store_root=roots["store2"], remote=roots["blob"],
                        budget=leg["search_budget"])
        warm_report = ff_warm.fit_resilient(
            {"x": xs}, ys, num_steps=steps + 1, batch_size=batch,
            directory=roots["ck_fresh"], resume=True,
        )
        warm_s = time.perf_counter() - t0
        assert warm_report.final_step == steps + 1
        warm_store_hit = bool(
            (ff_warm.strategy.search_stats or {}).get("store_hit")
        )

        t0 = time.perf_counter()
        # store_root="none" is the explicit opt-out: a bare None would
        # fall through to $FLEXFLOW_TPU_STORE_DIR and the "cold" compile
        # could warm-hit (and pollute) the user's fleet store
        ff_cold = build(store_root="none", budget=leg["search_budget"])
        cold_report = ff_cold.fit_resilient(
            {"x": xs}, ys, num_steps=1, batch_size=batch,
            directory=roots["ck_cold"],
        )
        cold_s = time.perf_counter() - t0
        assert cold_report.final_step == 1

        # post-bench verification: both tiers the drill produced must
        # fsck clean (every manifest crc, LATEST/REMOTE_LATEST intact)
        fsck = _fsck_verdict(local_dir=roots["ck_fresh"],
                             remote_uri=roots["blob"])
        assert fsck["ok"], fsck

        return {
            "workload": (
                f"{layers}L h{hidden} MLP, {steps} supervised steps, "
                f"checkpoint_every={every}, filesystem blob backend, "
                f"{n} devices"
            ),
            "step_ms_offload_off": round(step_ms_off, 2),
            "step_ms_offload_on": round(step_ms_on, 2),
            "offload_overhead_pct": round(
                (step_ms_on - step_ms_off) / max(step_ms_off, 1e-9) * 100, 1
            ),
            "offload_uploads": int(on_rep.counters["offload_uploads"]),
            "offload_bytes": int(on_rep.counters["offload_bytes"]),
            "recovery": {
                # fresh host: warm remote strategy store + remote restore
                "warm_remote_time_to_first_step_s": round(warm_s, 3),
                "warm_store_hit": warm_store_hit,
                "resumed_from_step": steps,
                # no remote tier: full search, training restarts at 0
                "cold_start_time_to_first_step_s": round(cold_s, 3),
                "progress_kept_steps": steps,
            },
            "fsck": fsck,
        }
    finally:
        for path in roots.values():
            shutil.rmtree(path, ignore_errors=True)


def bench_serving(dev, on_tpu):
    """Generation-serving throughput leg (manifest v10): the same
    mixed-length workload and Poisson arrival sequence through the
    STATIC tier (GenerationBatcher: coalesce -> one scan, every row
    padded to the batch's pow2 total bucket, dense per-slot caches)
    and the CONTINUOUS tier (ContinuousScheduler: iteration-level
    admit/retire on the paged KV pool).  Reports sustained tokens/s,
    p50/p99 TTFT and per-token latency, and the pool's peak block
    occupancy — the acceptance bar is continuous beating static on
    tokens/s under length heterogeneity."""
    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models.transformer import build_gpt
    from flexflow_tpu.serving import (ContinuousScheduler,
                                      GenerationBatcher,
                                      GenerationEngine)
    from flexflow_tpu.serving.loadgen import run_loadgen, sample_workload

    leg = MANIFEST["legs"]["serving"]
    if on_tpu:
        vocab, max_seq = leg["vocab"], leg["max_seq"]
        hidden, layers, heads = leg["hidden"], leg["layers"], leg["heads"]
        inter, slots = leg["intermediate"], leg["slots"]
        page, n_req = leg["kv_page_size"], leg["requests"]
        rate = leg["offered_rps"]
        plen_range = tuple(leg["prompt_len_range"])
        mnt_range = tuple(leg["max_new_range"])
        long_frac = leg["long_frac"]
        long_range = tuple(leg["long_max_new_range"])
    else:
        # saturating smoke load: offered rps well above service rate so
        # a backlog forms and tokens/s measures the SCHEDULER, not the
        # arrival process.  The model is sized so one decode step's
        # compute outweighs the continuous loop's per-step host
        # dispatch — the regime iteration-level batching targets (on
        # a real chip the model is orders of magnitude past this).
        # Reply lengths are heavy-tailed (75% short, 25% long), the
        # canonical serving distribution: one long request pads a
        # whole static batch to its bucket.
        vocab, max_seq = 128, 64
        hidden, layers, heads, inter = 256, 3, 8, 512
        slots, page, n_req, rate = 8, 8, 96, 600.0
        plen_range, mnt_range = (2, 12), (2, 10)
        long_frac, long_range = 0.25, (40, 56)

    cfg = FFConfig(batch_size=slots, num_devices=1)
    ff = FFModel(cfg)
    build_gpt(ff, batch_size=slots, seq_length=max_seq,
              hidden_size=hidden, num_layers=layers, num_heads=heads,
              intermediate_size=inter, vocab_size=vocab)
    ff.compile(optimizer=SGDOptimizer(lr=0.5),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=[dev])
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (slots, max_seq)).astype(np.int32)
    pos = np.broadcast_to(np.arange(max_seq, dtype=np.int32),
                          (slots, max_seq)).copy()
    ff.train_step({"input": ids, "positions": pos}, ids)  # real weights

    wl_rng = np.random.RandomState(11)
    workload = sample_workload(wl_rng, n_req, vocab,
                               prompt_len_range=plen_range,
                               max_new_range=mnt_range,
                               long_frac=long_frac,
                               long_max_new_range=long_range)

    # -- static tier: warm every pow2 total bucket the workload can hit
    static_engine = GenerationEngine(ff, batch_size=slots, devices=[dev])
    need = min(max_seq, max(len(p) + m for p, m in workload))
    bucket = 1
    while bucket < need:
        bucket <<= 1
        total = min(bucket, max_seq)
        static_engine.generate([workload[0][0][:2]],
                               max_new_tokens=total - 2)
    static_b = GenerationBatcher(static_engine, flush_timeout_s=0.02)
    try:
        static_report = run_loadgen(static_b, workload, rate, seed=7)
    finally:
        static_b.close()

    # -- continuous tier: one step program, one warmup request.
    # Equal-HBM sizing, the paged pool's actual pitch: the pool gets
    # exactly the block count whose bytes equal the static tier's
    # dense [slots, max_seq] caches, and the freed headroom becomes
    # 2x the decode slots — heterogeneous lengths mean the pool's
    # sum-of-live-lengths fits twice the sequences static can hold.
    max_blocks = max_seq // page
    sched = ContinuousScheduler.from_trained(
        ff, batch_slots=2 * slots, page_size=page,
        num_blocks=1 + slots * max_blocks, devices=[dev])
    try:
        sched.generate(workload[0][0], 2)  # pays the single compile
        cont_report = run_loadgen(sched, workload, rate, seed=7)
        pool_stats = sched.stats()["kv_pool"]
    finally:
        sched.close()

    ratio = (cont_report.get("tokens_per_s", 0.0)
             / max(static_report.get("tokens_per_s", 0.0), 1e-9))
    return {
        "workload": (
            f"{n_req} reqs, prompts {plen_range}, max_new {mnt_range}, "
            f"Poisson {rate} rps offered, greedy, {slots} slots, "
            f"page {page}"
        ),
        "static": static_report,
        "continuous": cont_report,
        "continuous_vs_static_tokens_per_s": round(ratio, 3),
        "kv_pool_peak_occupancy": round(
            pool_stats["peak_used_blocks"]
            / max(pool_stats["usable_blocks"], 1), 4),
        "kv_pool_peak_used_blocks": pool_stats["peak_used_blocks"],
        "kv_pool_usable_blocks": pool_stats["usable_blocks"],
    }


def bench_serving_prefix(dev, on_tpu):
    """Prefix-cache + chunked-prefill throughput leg (manifest v18):
    the SAME shared-prefix workload (K system prompts, per-request
    unique tails) and arrival sequence through the PR 6 continuous
    tier (sharing off, one-token prefill) and the prefix-cached tier
    (COW block sharing + [slots, C] chunked prefill) at EQUAL KV pool
    bytes.  Reports tokens/s both ways, p50/p99 TTFT, prefix-cache
    hit/shared/eviction counters and the shared-block high-water mark;
    asserts greedy completions byte-identical across modes, with the
    kv_pool invariant checker running at EVERY scheduler step of both
    runs.  Acceptance bar: >= 1.3x the baseline's tokens/s with lower
    p50 TTFT on the shared-prefix smoke workload."""
    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models.transformer import build_gpt
    from flexflow_tpu.serving import ContinuousScheduler
    from flexflow_tpu.serving.loadgen import (run_loadgen,
                                              sample_shared_prefix_workload)

    leg = MANIFEST["legs"]["serving_prefix"]
    if on_tpu:
        vocab, max_seq = leg["vocab"], leg["max_seq"]
        hidden, layers, heads = leg["hidden"], leg["layers"], leg["heads"]
        inter, slots = leg["intermediate"], leg["slots"]
        page, n_req = leg["kv_page_size"], leg["requests"]
        rate, chunk = leg["offered_rps"], leg["prefill_chunk"]
        n_prefixes, prefix_len = leg["num_prefixes"], leg["prefix_len"]
        tail_range = tuple(leg["tail_range"])
        mnt_range = tuple(leg["max_new_range"])
    else:
        # prefill-heavy smoke shape: long shared prefixes (half the
        # position table), short unique tails and replies — the
        # system-prompt regime where the PR 6 tier burns most of its
        # steps re-prefilling identical tokens one at a time
        vocab, max_seq = 128, 64
        hidden, layers, heads, inter = 256, 3, 8, 512
        slots, page, n_req, rate, chunk = 8, 8, 64, 600.0, 8
        n_prefixes, prefix_len = 4, 32
        tail_range, mnt_range = (1, 7), (2, 8)

    cfg = FFConfig(batch_size=slots, num_devices=1)
    ff = FFModel(cfg)
    build_gpt(ff, batch_size=slots, seq_length=max_seq,
              hidden_size=hidden, num_layers=layers, num_heads=heads,
              intermediate_size=inter, vocab_size=vocab)
    ff.compile(optimizer=SGDOptimizer(lr=0.5),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=[dev])
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (slots, max_seq)).astype(np.int32)
    pos = np.broadcast_to(np.arange(max_seq, dtype=np.int32),
                          (slots, max_seq)).copy()
    ff.train_step({"input": ids, "positions": pos}, ids)  # real weights

    wl_rng = np.random.RandomState(23)
    workload, prefixes = sample_shared_prefix_workload(
        wl_rng, n_req, vocab, num_prefixes=n_prefixes,
        prefix_len=prefix_len, tail_range=tail_range,
        max_new_range=mnt_range)

    # equal-HBM pitch (the serving leg's): both pools get the block
    # bytes of a dense [slots, max_seq] cache, spent on 2x slots
    max_blocks = max_seq // page
    num_blocks = 1 + slots * max_blocks
    warm_rng = np.random.RandomState(999)
    warm = warm_rng.randint(0, vocab, page).tolist()  # 1 aligned page

    def run_tier(prefix_cache, prefill_chunk):
        sched = ContinuousScheduler.from_trained(
            ff, batch_slots=2 * slots, page_size=page,
            num_blocks=num_blocks, devices=[dev],
            prefix_cache=prefix_cache, prefill_chunk=prefill_chunk,
            check_invariants=True)  # invariant sweep at EVERY step
        try:
            # warm every program before timing: decode, chunked
            # prefill, and (second warm call = full-prompt hit) the
            # COW block copy.  The warm prompt is disjoint from the
            # workload prefixes.
            sched.generate(warm, 2, timeout=120.0)
            sched.generate(warm, 2, timeout=120.0)
            report = run_loadgen(sched, workload, rate, seed=13,
                                 detail=True, record_tokens=True)
            stats = sched.stats()
            sched.pool.check_invariants()
            return report, stats
        finally:
            sched.close()

    base_report, base_stats = run_tier(False, 0)
    prefix_report, prefix_stats = run_tier(True, chunk)

    # greedy completions must be byte-identical across modes
    def by_idx(report):
        return {r["idx"]: r["tokens"] for r in report["records"]
                if r.get("ok")}
    base_toks, prefix_toks = by_idx(base_report), by_idx(prefix_report)
    assert set(base_toks) == set(prefix_toks), "completion sets differ"
    mismatched = sum(1 for i in base_toks
                     if base_toks[i] != prefix_toks[i])
    assert mismatched == 0, \
        f"{mismatched} completions differ between sharing on/off"

    hit_total = sum(r.get("prefix_hit_tokens", 0)
                    for r in prefix_report["records"])
    ratio = (prefix_report.get("tokens_per_s", 0.0)
             / max(base_report.get("tokens_per_s", 0.0), 1e-9))
    pc = prefix_stats["prefix_cache"]
    return {
        "workload": (
            f"{n_req} reqs over {n_prefixes} shared {prefix_len}-token "
            f"prefixes, tails {tail_range}, max_new {mnt_range}, "
            f"Poisson {rate} rps, greedy, {2 * slots} slots, "
            f"page {page}, chunk {chunk}, equal KV bytes"
        ),
        "baseline": base_report,
        "prefix_cached": prefix_report,
        "prefix_vs_baseline_tokens_per_s": round(ratio, 3),
        "speedup_at_least_1_3": bool(ratio >= 1.3),
        "ttft_p50_lower": bool(
            prefix_report.get("ttft", {}).get("p50_ms", 1e9)
            < base_report.get("ttft", {}).get("p50_ms", 0.0)),
        "prefix_hit_tokens": hit_total,
        "prefix_cache": pc,
        "kv_shared_blocks_high_water": pc["peak_shared_blocks"],
        "prefill_steps": prefix_stats["prefill_steps"],
        "completions_identical": True,  # asserted above
        "invariants_checked_every_step": True,  # check_invariants=True
    }


def bench_serving_paged_kernel(dev, on_tpu):
    """Fused PagedAttention leg (manifest v19): the SAME shared-prefix
    workload and arrival gaps through the paged continuous tier under
    both READ formulations at equal KV pool bytes — `gather` (the
    dense block-gather oracle) vs `pallas` (the fused kernel streaming
    blocks in place, ops/pallas/paged_attention.py; interpret-mode off
    TPU, so the CPU smoke's tokens/s ratio measures the emulator, not
    the kernel).  Asserts greedy completions token-identical across
    formulations and that the kernel's per-step KV reads undercut the
    dense-gather equivalent — blocks read scale with live tokens, not
    the table width (the serving/paged_kernel_* telemetry)."""
    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models.transformer import build_gpt
    from flexflow_tpu.serving import ContinuousScheduler
    from flexflow_tpu.serving.loadgen import (run_loadgen,
                                              sample_shared_prefix_workload)

    leg = MANIFEST["legs"]["serving_paged_kernel"]
    if on_tpu:
        vocab, max_seq = leg["vocab"], leg["max_seq"]
        hidden, layers, heads = leg["hidden"], leg["layers"], leg["heads"]
        inter, slots = leg["intermediate"], leg["slots"]
        page, n_req = leg["kv_page_size"], leg["requests"]
        rate, chunk = leg["offered_rps"], leg["prefill_chunk"]
        n_prefixes, prefix_len = leg["num_prefixes"], leg["prefix_len"]
        tail_range = tuple(leg["tail_range"])
        mnt_range = tuple(leg["max_new_range"])
    else:
        # small smoke shape: the interpret-mode kernel emulates every
        # grid program, so keep rows * heads * table width modest
        vocab, max_seq = 128, 64
        hidden, layers, heads, inter = 128, 2, 4, 256
        slots, page, n_req, rate, chunk = 4, 8, 24, 400.0, 8
        n_prefixes, prefix_len = 3, 24
        tail_range, mnt_range = (1, 7), (2, 8)

    cfg = FFConfig(batch_size=slots, num_devices=1)
    ff = FFModel(cfg)
    build_gpt(ff, batch_size=slots, seq_length=max_seq,
              hidden_size=hidden, num_layers=layers, num_heads=heads,
              intermediate_size=inter, vocab_size=vocab)
    ff.compile(optimizer=SGDOptimizer(lr=0.5),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=[dev])
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (slots, max_seq)).astype(np.int32)
    pos = np.broadcast_to(np.arange(max_seq, dtype=np.int32),
                          (slots, max_seq)).copy()
    ff.train_step({"input": ids, "positions": pos}, ids)  # real weights

    wl_rng = np.random.RandomState(31)
    workload, _ = sample_shared_prefix_workload(
        wl_rng, n_req, vocab, num_prefixes=n_prefixes,
        prefix_len=prefix_len, tail_range=tail_range,
        max_new_range=mnt_range)
    max_blocks = max_seq // page
    num_blocks = 1 + slots * max_blocks  # identical KV HBM both tiers
    warm = np.random.RandomState(999).randint(0, vocab, page).tolist()

    def run_tier(paged_kernel):
        sched = ContinuousScheduler.from_trained(
            ff, batch_slots=2 * slots, page_size=page,
            num_blocks=num_blocks, devices=[dev],
            prefix_cache=True, prefill_chunk=chunk,
            paged_kernel=paged_kernel, check_invariants=True)
        try:
            sched.generate(warm, 2, timeout=120.0)
            sched.generate(warm, 2, timeout=120.0)  # full-hit COW warm
            report = run_loadgen(sched, workload, rate, seed=17,
                                 detail=True, record_tokens=True)
            return report, sched.stats()
        finally:
            sched.close()

    gather_report, gather_stats = run_tier("gather")
    kernel_report, kernel_stats = run_tier("pallas")

    def by_idx(report):
        return {r["idx"]: r["tokens"] for r in report["records"]
                if r.get("ok")}
    g_toks, k_toks = by_idx(gather_report), by_idx(kernel_report)
    assert set(g_toks) == set(k_toks), "completion sets differ"
    mismatched = sum(1 for i in g_toks if g_toks[i] != k_toks[i])
    assert mismatched == 0, \
        f"{mismatched} completions differ gather vs kernel"

    pk = kernel_stats["paged_kernel"]
    assert pk["formulation"] == "pallas"
    # THE traffic acceptance: per-step KV reads follow live tokens,
    # not slots * table_width (what the dense gather materializes)
    assert 0 < pk["blocks_read"] < pk["dense_blocks_equiv"], pk
    dispatches = (kernel_stats["steps"]
                  + kernel_stats["prefill_steps"] * chunk)
    ratio = (kernel_report.get("tokens_per_s", 0.0)
             / max(gather_report.get("tokens_per_s", 0.0), 1e-9))
    return {
        "workload": (
            f"{n_req} reqs over {n_prefixes} shared {prefix_len}-token "
            f"prefixes, tails {tail_range}, max_new {mnt_range}, "
            f"Poisson {rate} rps, greedy, {2 * slots} slots, "
            f"page {page}, chunk {chunk}, equal KV pool bytes"
        ),
        "gather": gather_report,
        "pallas": kernel_report,
        "kernel_vs_gather_tokens_per_s": round(ratio, 3),
        "kernel_real_on_this_backend": bool(on_tpu),  # CPU = interpreter
        "kv_blocks_read": pk["blocks_read"],
        "kv_dense_blocks_equiv": pk["dense_blocks_equiv"],
        "kv_read_fraction_of_dense": round(
            pk["blocks_read"] / max(pk["dense_blocks_equiv"], 1), 4),
        "kv_bytes_read": pk["bytes_read"],
        "kv_dense_bytes_avoided": pk["dense_bytes_avoided"],
        "kv_bytes_read_per_dispatch": round(
            pk["bytes_read"] / max(dispatches, 1), 1),
        "completions_identical": True,   # asserted above
        "reads_scale_with_live_tokens": True,  # asserted above
        "invariants_checked_every_step": True,  # check_invariants=True
    }


def bench_serving_gspmd(dev, on_tpu):
    """GSPMD tensor-parallel serving leg (manifest v20): the shared-
    prefix workload through the paged continuous tier single-chip
    (tp=1) and on a 2-chip replica mesh (tp=2) at EQUAL PER-CHIP KV
    POOL BYTES.  Head-sharded pools halve each block's per-chip bytes,
    so the tp=2 engine funds 2x the blocks — and 2x the decode slots —
    in the same per-chip HBM; the host-owned block-table machinery
    (prefix sharing, COW, chunked prefill) runs unchanged on the
    sharded physical blocks.  Greedy completions are asserted
    token-identical across degrees (the single-chip gather formulation
    is the oracle) with the kv_pool invariant checker at EVERY
    scheduler step of both runs.  Off TPU the mesh is virtual CPU
    devices, so tokens/s measures emulated collectives; the capacity
    (2x slots at equal per-chip bytes) + identity assertions are the
    acceptance bar."""
    import jax

    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models.transformer import build_gpt
    from flexflow_tpu.serving import ContinuousScheduler
    from flexflow_tpu.serving.loadgen import (run_loadgen,
                                              sample_shared_prefix_workload)

    leg = MANIFEST["legs"]["serving_gspmd"]
    devs = jax.devices()
    tp = leg["tp"]
    if len(devs) < tp:
        return {"skipped": (f"needs >= {tp} visible devices for the "
                            f"tp={tp} replica, have {len(devs)}")}
    if on_tpu:
        vocab, max_seq = leg["vocab"], leg["max_seq"]
        hidden, layers, heads = leg["hidden"], leg["layers"], leg["heads"]
        inter, slots = leg["intermediate"], leg["slots"]
        page, n_req = leg["kv_page_size"], leg["requests"]
        rate, chunk = leg["offered_rps"], leg["prefill_chunk"]
        n_prefixes, prefix_len = leg["num_prefixes"], leg["prefix_len"]
        tail_range = tuple(leg["tail_range"])
        mnt_range = tuple(leg["max_new_range"])
    else:
        # two engines compile (one under GSPMD search), so the smoke
        # shape is smaller than serving_prefix's
        vocab, max_seq = 64, 32
        hidden, layers, heads, inter = 64, 2, 4, 128
        slots, page, n_req, rate, chunk = 4, 4, 24, 600.0, 4
        n_prefixes, prefix_len = 2, 8
        tail_range, mnt_range = (1, 5), (2, 6)

    cfg = FFConfig(batch_size=slots, num_devices=1)
    ff = FFModel(cfg)
    build_gpt(ff, batch_size=slots, seq_length=max_seq,
              hidden_size=hidden, num_layers=layers, num_heads=heads,
              intermediate_size=inter, vocab_size=vocab)
    ff.compile(optimizer=SGDOptimizer(lr=0.5),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=devs[:1])
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (slots, max_seq)).astype(np.int32)
    pos = np.broadcast_to(np.arange(max_seq, dtype=np.int32),
                          (slots, max_seq)).copy()
    ff.train_step({"input": ids, "positions": pos}, ids)  # real weights

    wl_rng = np.random.RandomState(29)
    workload, _ = sample_shared_prefix_workload(
        wl_rng, n_req, vocab, num_prefixes=n_prefixes,
        prefix_len=prefix_len, tail_range=tail_range,
        max_new_range=mnt_range)

    # equal PER-CHIP bytes: each tp=2 block costs 1/2 per chip, so the
    # 2-chip pool funds 2x the blocks — spent on 2x the decode slots
    max_blocks = max_seq // page
    base_blocks = 1 + slots * max_blocks

    def run_degree(degree, n_slots, n_blocks):
        sched = ContinuousScheduler.from_trained(
            ff, batch_slots=n_slots, page_size=page,
            num_blocks=n_blocks, devices=devs[:degree],
            prefix_cache=True, prefill_chunk=chunk,
            check_invariants=True, tp=degree)  # audit at EVERY step
        try:
            report = run_loadgen(sched, workload, rate, seed=17,
                                 detail=True, record_tokens=True)
            stats = sched.stats()
            sched.pool.check_invariants()
            return report, stats
        finally:
            sched.close()

    base_report, base_stats = run_degree(1, slots, base_blocks)
    tp_report, tp_stats = run_degree(tp, tp * slots, tp * base_blocks)

    # greedy completions token-identical across degrees: the
    # single-chip gather formulation is the oracle
    def by_idx(report):
        return {r["idx"]: r["tokens"] for r in report["records"]
                if r.get("ok")}
    base_toks, tp_toks = by_idx(base_report), by_idx(tp_report)
    assert set(base_toks) == set(tp_toks), "completion sets differ"
    mismatched = sum(1 for i in base_toks
                     if base_toks[i] != tp_toks[i])
    assert mismatched == 0, \
        f"{mismatched} completions differ between tp=1 and tp={tp}"

    # the headline capacity claim, checked on the telemetry the
    # engines themselves report
    per_chip_1 = base_stats["tp"]["kv_pool_bytes_per_chip"]
    per_chip_tp = tp_stats["tp"]["kv_pool_bytes_per_chip"]
    assert per_chip_tp == per_chip_1, \
        f"per-chip pool bytes differ: {per_chip_1} vs {per_chip_tp}"
    assert tp_stats["tp"]["degree"] == tp
    assert tp_stats["tp"]["kv_block_bytes_per_chip"] * tp == \
        tp_stats["tp"]["kv_block_bytes"]

    ratio = (tp_report.get("tokens_per_s", 0.0)
             / max(base_report.get("tokens_per_s", 0.0), 1e-9))
    return {
        "workload": (
            f"{n_req} reqs over {n_prefixes} shared {prefix_len}-token "
            f"prefixes, tails {tail_range}, max_new {mnt_range}, "
            f"Poisson {rate} rps, greedy, page {page}, chunk {chunk}, "
            f"tp=1 ({slots} slots, {base_blocks} blocks) vs tp={tp} "
            f"({tp * slots} slots, {tp * base_blocks} blocks) at equal "
            f"per-chip KV bytes"
        ),
        "tp1": base_report,
        f"tp{tp}": tp_report,
        "tp_vs_tp1_tokens_per_s": round(ratio, 3),
        "kv_pool_bytes_per_chip": per_chip_1,
        "per_chip_bytes_equal": True,    # asserted above
        "slots": {"tp1": slots, f"tp{tp}": tp * slots},
        "slots_ratio_at_equal_per_chip_hbm": float(tp),
        "replica_mesh": tp_stats["tp"]["mesh_shape"],
        "prefix_cache_tp": tp_stats["prefix_cache"],
        "completions_identical": True,   # asserted above
        "invariants_checked_every_step": True,  # check_invariants=True
    }


def bench_serving_resilience(dev, on_tpu):
    """Replicated-front availability leg (manifest v12): the Poisson
    workload of the serving leg against a 2-replica ServingFront with
    a SEEDED replica kill (injected hung decode step -> StepWatchdog
    taxonomy -> supervised restart) fired mid-run.  Reports
    availability (completed/submitted — the acceptance bar is >= 0.99
    with the fault injected), p99 TTFT before/during/after the fault
    window, recovery time, and the requeue/restart counters.  Greedy
    decoding keeps every completion token-identical to a fault-free
    run — the front requeues stranded requests instead of failing
    them."""
    import time as _time

    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models.transformer import build_gpt
    from flexflow_tpu.obs.metrics import MetricsRegistry
    from flexflow_tpu.resilience.faults import FaultKind, FaultPlan
    from flexflow_tpu.serving import ServingFront
    from flexflow_tpu.serving.loadgen import run_loadgen, sample_workload

    leg = MANIFEST["legs"]["serving_resilience"]
    if on_tpu:
        vocab, max_seq = leg["vocab"], leg["max_seq"]
        hidden, layers, heads = leg["hidden"], leg["layers"], leg["heads"]
        inter, slots = leg["intermediate"], leg["slots"]
        page, n_req = leg["kv_page_size"], leg["requests"]
        rate, kill_step = leg["offered_rps"], leg["kill_step"]
        plen_range = tuple(leg["prompt_len_range"])
        mnt_range = tuple(leg["max_new_range"])
    else:
        vocab, max_seq = 64, 64
        hidden, layers, heads, inter = 128, 2, 4, 256
        slots, page, n_req, rate = 4, 8, 48, 400.0
        plen_range, mnt_range = (2, 8), (2, 10)
        kill_step = 80  # ~mid-run: the smoke workload spans ~150 steps

    cfg = FFConfig(batch_size=slots, num_devices=1,
                   serving_slots=slots, kv_page_size=page,
                   serving_replicas=2, serving_step_timeout=0.0,
                   serving_max_restarts=3, request_retry_limit=3)
    ff = FFModel(cfg)
    build_gpt(ff, batch_size=slots, seq_length=max_seq,
              hidden_size=hidden, num_layers=layers, num_heads=heads,
              intermediate_size=inter, vocab_size=vocab)
    ff.compile(optimizer=SGDOptimizer(lr=0.5),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=[dev])
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (slots, max_seq)).astype(np.int32)
    pos = np.broadcast_to(np.arange(max_seq, dtype=np.int32),
                          (slots, max_seq)).copy()
    ff.train_step({"input": ids, "positions": pos}, ids)  # real weights

    reg = MetricsRegistry()
    front = ServingFront.from_trained(
        ff, devices=[dev], registry=reg, retry_backoff=0.01,
        fault_plans={0: FaultPlan.single(kill_step,
                                         FaultKind.HUNG_STEP)},
    )
    try:
        # warm BOTH replicas' decode-step compiles before timing: more
        # concurrent warm requests than one replica's slots forces the
        # dispatcher to spread them
        warm = [front.generate_async([1, 2], 2)
                for _ in range(2 * slots)]
        for h in warm:
            h.wait(300.0)
        wl_rng = np.random.RandomState(11)
        workload = sample_workload(wl_rng, n_req, vocab,
                                   prompt_len_range=plen_range,
                                   max_new_range=mnt_range)
        t0 = _time.monotonic()
        report = run_loadgen(front, workload, rate, seed=7,
                             detail=True)
        rep0 = front.replicas[0]
        # the rebuild pays a decode-twin compile, which can outlast a
        # short smoke run — wait it out so recovery time is recorded
        deadline = _time.monotonic() + 120.0
        while (_time.monotonic() < deadline
               and rep0.state == "restarting"):
            _time.sleep(0.05)
        death_s = (rep0.last_death_t - t0
                   if rep0.last_death_t is not None else None)
        recover_s = (rep0.last_live_t - t0
                     if rep0.last_death_t is not None
                     and rep0.last_live_t is not None
                     and rep0.last_live_t > rep0.last_death_t else None)
    finally:
        front.close()

    def p99(vals):
        return (round(float(np.percentile(vals, 99)) * 1e3, 2)
                if vals else None)

    records = report.pop("records", [])
    # the fault window runs from the death until the replica is LIVE
    # again; on short smoke runs recovery can postdate the last request
    fault_end = recover_s if recover_s is not None else float("inf")
    before = [r["ttft_s"] for r in records
              if r.get("ok") and death_s is not None
              and r["submit_s"] < death_s]
    during = [r["ttft_s"] for r in records
              if r.get("ok") and death_s is not None
              and death_s <= r["submit_s"] < fault_end]
    after = [r["ttft_s"] for r in records
             if r.get("ok") and recover_s is not None
             and r["submit_s"] >= fault_end]
    availability = report["completed"] / max(report["requests"], 1)
    return {
        "workload": (
            f"{n_req} reqs, Poisson {rate} rps, 2 replicas, "
            f"seeded replica-0 kill at decode step {kill_step}"
        ),
        "availability": round(availability, 4),
        "completed": report["completed"],
        "submitted": report["requests"],
        "failures": report["failures"],
        "fault": {
            "death_at_s": round(death_s, 3) if death_s is not None else None,
            "recovery_s": (round(rep0.last_recovery_s, 3)
                           if rep0.last_recovery_s is not None else None),
            "replica_deaths": sum(r.deaths for r in front.replicas),
            "replica_restarts": sum(r.restarts for r in front.replicas),
            "requeued_requests": front.requeued_requests,
        },
        "ttft_p99_ms": {
            "before_fault": p99(before),
            "during_fault": p99(during),
            "after_recovery": p99(after),
        },
        "tokens_per_s": report.get("tokens_per_s", 0.0),
    }


def bench_serving_disagg(dev, on_tpu):
    """Disaggregated prefill/decode fleet leg (manifest v21): the
    shared-prefix workload plus a sub-page prompt mix through a
    1-prefill + 1-decode DisaggServingFront vs the colocated 2-mixed
    ServingFront at EQUAL TOTAL CHIPS.  Multi-page prompts land on the
    migrate side of the dispatcher's cost model (KV blocks stream
    replica-to-replica and re-enter as a prefix-cache hit on the
    decode class); sub-page prompts have nothing block-aligned to ship
    and re-prefill — the leg asserts BOTH decisions fire, and that
    greedy completions are TOKEN-IDENTICAL between the two fleets (the
    colocated front is the oracle).  Reports per-class TTFT/per-token
    latency, migration decision/bytes counters, and the tokens/s
    ratio.  docs/SERVING.md "Disaggregated fleet"."""
    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models.transformer import build_gpt
    from flexflow_tpu.obs.metrics import MetricsRegistry
    from flexflow_tpu.serving import DisaggServingFront, ServingFront
    from flexflow_tpu.serving.loadgen import (
        run_loadgen, sample_shared_prefix_workload, sample_workload)

    leg = MANIFEST["legs"]["serving_disagg"]
    if on_tpu:
        vocab, max_seq = leg["vocab"], leg["max_seq"]
        hidden, layers, heads = leg["hidden"], leg["layers"], leg["heads"]
        inter, slots = leg["intermediate"], leg["slots"]
        page, n_req = leg["kv_page_size"], leg["requests"]
        rate, chunk = leg["offered_rps"], leg["prefill_chunk"]
        n_prefixes, prefix_len = leg["num_prefixes"], leg["prefix_len"]
        tail_range = tuple(leg["tail_range"])
        mnt_range = tuple(leg["max_new_range"])
        n_sub = leg["subpage_requests"]
        sub_range = tuple(leg["subpage_len_range"])
    else:
        vocab, max_seq = 64, 32
        hidden, layers, heads, inter = 64, 2, 4, 128
        slots, page, n_req, rate, chunk = 4, 4, 24, 400.0, 4
        n_prefixes, prefix_len = 2, 8
        tail_range, mnt_range = (1, 4), (2, 6)
        n_sub, sub_range = 8, (2, 4)

    cfg = FFConfig(batch_size=slots, num_devices=1,
                   serving_slots=slots, kv_page_size=page,
                   serving_replicas=2, prefill_chunk=chunk)
    ff = FFModel(cfg)
    build_gpt(ff, batch_size=slots, seq_length=max_seq,
              hidden_size=hidden, num_layers=layers, num_heads=heads,
              intermediate_size=inter, vocab_size=vocab)
    ff.compile(optimizer=SGDOptimizer(lr=0.5),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=[dev])
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (slots, max_seq)).astype(np.int32)
    pos = np.broadcast_to(np.arange(max_seq, dtype=np.int32),
                          (slots, max_seq)).copy()
    ff.train_step({"input": ids, "positions": pos}, ids)  # real weights

    wl_rng = np.random.RandomState(31)
    shared_wl, _ = sample_shared_prefix_workload(
        wl_rng, n_req, vocab, num_prefixes=n_prefixes,
        prefix_len=prefix_len, tail_range=tail_range,
        max_new_range=mnt_range)
    # sub-page prompts: nothing block-aligned to ship — the cost
    # model's guaranteed re-prefill side
    sub_wl = sample_workload(wl_rng, n_sub, vocab,
                             prompt_len_range=sub_range,
                             max_new_range=mnt_range)
    workload = shared_wl + sub_wl

    def run_front(front):
        try:
            # warm every replica's compiles off the clock: a sub-page
            # prompt exercises the direct decode path, a multi-page
            # one the prefill pass + migration path
            warm = [front.generate_async([1, 2], 2)
                    for _ in range(2 * slots)]
            warm.append(front.generate_async(
                list(range(1, 2 * page + 2)), 2))
            for h in warm:
                h.wait(300.0)
            report = run_loadgen(front, workload, rate, seed=19,
                                 detail=True, record_tokens=True)
            return report, front.stats()
        finally:
            front.close()

    colo_report, _ = run_front(ServingFront.from_trained(
        ff, devices=[dev]))
    reg = MetricsRegistry()
    disagg_report, disagg_stats = run_front(
        DisaggServingFront.from_trained(
            ff, num_replicas=2, devices=[dev],
            roles=["prefill", "decode"], registry=reg))

    # greedy completions token-identical: the colocated fleet is the
    # oracle, migration is invisible in the output stream
    def by_idx(report):
        return {r["idx"]: r["tokens"] for r in report["records"]
                if r.get("ok")}
    colo_toks, disagg_toks = by_idx(colo_report), by_idx(disagg_report)
    assert set(colo_toks) == set(disagg_toks), "completion sets differ"
    mismatched = sum(1 for i in colo_toks
                     if colo_toks[i] != disagg_toks[i])
    assert mismatched == 0, \
        f"{mismatched} completions differ colocated vs disaggregated"

    dg = disagg_stats["disagg"]
    # both dispatcher decisions must fire, or the leg measured only
    # half the machinery
    assert dg["migrate_decisions"] > 0, "no migration was ever chosen"
    assert dg["reprefill_decisions"] > 0, \
        "no re-prefill was ever chosen (sub-page mix missing?)"
    roles = disagg_stats["roles"]
    for r in colo_report, disagg_report:
        r.pop("records", None)
    ratio = (disagg_report.get("tokens_per_s", 0.0)
             / max(colo_report.get("tokens_per_s", 0.0), 1e-9))
    return {
        "workload": (
            f"{n_req} shared-prefix reqs ({n_prefixes} x "
            f"{prefix_len}-token prefixes, tails {tail_range}) + "
            f"{n_sub} sub-page reqs {sub_range}, max_new {mnt_range}, "
            f"Poisson {rate} rps, greedy, page {page}, chunk {chunk}; "
            f"colocated 2-mixed vs prefill=1,decode=1 at equal chips"
        ),
        "colocated": colo_report,
        "disaggregated": disagg_report,
        "disagg_vs_colocated_tokens_per_s": round(ratio, 3),
        "decisions": {
            "migrate": dg["migrate_decisions"],
            "reprefill": dg["reprefill_decisions"],
            "migrations_ok": dg["migrations_ok"],
            "migrations_failed": dg["migrations_failed"],
        },
        "kv_transfer": dg["kv_transfer"],
        "per_class": {
            role: {
                "replicas": st["replicas"],
                "ttft_ms": st["ttft"],
                "per_token_ms": st["per_token"],
                "service_rate_rps": st["service_rate_rps"],
            } for role, st in roles.items()
        },
        "completions_identical": True,  # asserted above
        "both_decisions_exercised": True,  # asserted above
    }


def bench_serving_spec(dev, on_tpu):
    """Speculative-decoding leg (manifest v22): the SAME repetitive
    workload (sample_repetitive_workload: phrase-pool prompts with
    high n-gram self-overlap) and arrival sequence through four tiers
    at EQUAL KV pool bytes — the PR 6 continuous tier (no sharing,
    one-token prefill), the PR 14 tier (prefix cache + chunked
    prefill, `--spec-decode off`), and the PR 14 tier under
    `--spec-decode ngram` and `draft` (a 1-layer draft GPT trained on
    the same data).  The target is TRAINED on the phrase distribution
    so its greedy generations keep quoting phrases the context already
    contains — the regime prompt-lookup speculation feeds on.  Asserts
    greedy completions byte-identical across ALL modes (verify rides
    the lax.scan chunk twin, so acceptance is token-identical by
    construction) with the kv_pool invariant checker at every step,
    and that the speculative tiers accept > 1.5 draft tokens per
    verify round.  Reports tokens/s per tier, accept rates, and
    accepted-tokens/round."""
    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models.transformer import build_gpt
    from flexflow_tpu.serving import ContinuousScheduler
    from flexflow_tpu.serving.loadgen import (run_loadgen,
                                              sample_repetitive_workload)

    leg = MANIFEST["legs"]["serving_spec"]
    if on_tpu:
        vocab, max_seq = leg["vocab"], leg["max_seq"]
        hidden, layers, heads = leg["hidden"], leg["layers"], leg["heads"]
        inter, slots = leg["intermediate"], leg["slots"]
        page, n_req = leg["kv_page_size"], leg["requests"]
        rate, chunk = leg["offered_rps"], leg["prefill_chunk"]
        spec_k = leg["spec_k"]
        n_tpl, ppt = leg["num_templates"], leg["phrases_per_template"]
        phrase_len = leg["phrase_len"]
        phrases_range = tuple(leg["prompt_phrases_range"])
        mnt_range = tuple(leg["max_new_range"])
        d_hidden, d_layers = leg["draft_hidden"], leg["draft_layers"]
        d_heads, d_inter = leg["draft_heads"], leg["draft_intermediate"]
        train_steps = leg["train_steps"]
    else:
        # smoke shape: a tiny vocab and a 4-phrase pool so both models
        # MEMORIZE the phrase grammar in a few hundred SGD steps —
        # within-phrase continuations become deterministic, which is
        # what makes the n-gram drafts keep getting accepted
        vocab, max_seq = 32, 64
        hidden, layers, heads, inter = 128, 2, 4, 256
        slots, page, n_req, rate, chunk = 8, 8, 24, 600.0, 8
        spec_k = 4
        n_tpl, ppt, phrase_len = 2, 2, 8
        phrases_range, mnt_range = (3, 5), (8, 16)
        d_hidden, d_layers, d_heads, d_inter = 32, 1, 2, 64
        train_steps = 300

    wl_rng = np.random.RandomState(23)
    workload, _ = sample_repetitive_workload(
        wl_rng, n_req, vocab, num_templates=n_tpl,
        phrases_per_template=ppt, phrase_len=phrase_len,
        prompt_phrases_range=phrases_range, max_new_range=mnt_range)

    # training corpus from the SAME phrase pools: a fresh seed-23 rng
    # redraws the identical pools (they come from the stream's first
    # draws), and long phrase chains trimmed to max_seq+1 give the
    # next-token rows that teach both models the phrase grammar
    n_phrases_per_row = -(-(max_seq + 1) // phrase_len)  # ceil
    corpus_reqs, _ = sample_repetitive_workload(
        np.random.RandomState(23), 256, vocab, num_templates=n_tpl,
        phrases_per_template=ppt, phrase_len=phrase_len,
        prompt_phrases_range=(n_phrases_per_row, n_phrases_per_row))
    corpus = np.stack([np.asarray(p[:max_seq + 1], np.int32)
                       for p, _ in corpus_reqs])

    def phrase_rows(rng, n_rows):
        return corpus[rng.randint(len(corpus), size=n_rows)]

    pos = np.broadcast_to(np.arange(max_seq, dtype=np.int32),
                          (slots, max_seq)).copy()

    def make_model(h, n_layers, n_heads, i):
        cfg = FFConfig(batch_size=slots, num_devices=1)
        ff = FFModel(cfg)
        build_gpt(ff, batch_size=slots, seq_length=max_seq,
                  hidden_size=h, num_layers=n_layers, num_heads=n_heads,
                  intermediate_size=i, vocab_size=vocab)
        ff.compile(optimizer=SGDOptimizer(lr=0.5),
                   loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                   devices=[dev])
        rng = np.random.RandomState(7)
        for _ in range(train_steps):
            rows = phrase_rows(rng, slots)
            ff.train_step({"input": rows[:, :-1], "positions": pos},
                          rows[:, 1:])
        return ff

    ff = make_model(hidden, layers, heads, inter)
    draft_ff = make_model(d_hidden, d_layers, d_heads, d_inter)

    # equal-HBM pitch across all four tiers
    max_blocks = max_seq // page
    num_blocks = 1 + slots * max_blocks
    warm_rng = np.random.RandomState(999)
    warm = warm_rng.randint(0, vocab, page).tolist()

    def run_tier(prefix_cache, prefill_chunk, spec, d_ff=None):
        sched = ContinuousScheduler.from_trained(
            ff, batch_slots=slots, page_size=page,
            num_blocks=num_blocks, devices=[dev],
            prefix_cache=prefix_cache, prefill_chunk=prefill_chunk,
            spec_decode=spec, spec_k=spec_k, draft_ff=d_ff,
            check_invariants=True)  # invariant sweep at EVERY step
        try:
            sched.generate(warm, 2, timeout=120.0)
            sched.generate(warm, 2, timeout=120.0)
            report = run_loadgen(sched, workload, rate, seed=13,
                                 detail=True, record_tokens=True)
            stats = sched.stats()
            sched.pool.check_invariants()
            return report, stats
        finally:
            sched.close()

    pr6_report, _ = run_tier(False, 0, "off")
    off_report, off_stats = run_tier(True, chunk, "off")
    ngram_report, ngram_stats = run_tier(True, chunk, "ngram")
    draft_report, draft_stats = run_tier(True, chunk, "draft", draft_ff)

    # greedy completions must be byte-identical across ALL modes
    def by_idx(report):
        return {r["idx"]: r["tokens"] for r in report["records"]
                if r.get("ok")}
    base_toks = by_idx(off_report)
    for name, rep in (("pr6", pr6_report), ("ngram", ngram_report),
                      ("draft", draft_report)):
        toks = by_idx(rep)
        assert set(toks) == set(base_toks), \
            f"{name}: completion set differs from spec-off"
        bad = sum(1 for i in base_toks if toks[i] != base_toks[i])
        assert bad == 0, f"{name}: {bad} completions differ from spec-off"

    for name, st in (("ngram", ngram_stats), ("draft", draft_stats)):
        spec = st["speculative"]
        assert spec["rounds"] > 0, f"{name}: no verify rounds ran"
        assert spec["accepted_per_round"] > 1.5, \
            (f"{name}: accepted-tokens/round "
             f"{spec['accepted_per_round']} <= 1.5")
        assert not spec["degraded"], f"{name}: engine degraded"

    def tps(rep):
        return rep.get("tokens_per_s", 0.0)

    return {
        "workload": (
            f"{n_req} reqs, {n_tpl} templates x {ppt} phrases x "
            f"{phrase_len} tokens, {phrases_range} phrases/prompt, "
            f"max_new {mnt_range}, Poisson {rate} rps, greedy, "
            f"{slots} slots, page {page}, chunk {chunk}, k {spec_k}, "
            f"equal KV bytes"
        ),
        "pr6_baseline": pr6_report,
        "off": off_report,
        "ngram": ngram_report,
        "draft": draft_report,
        "ngram_speculative": ngram_stats["speculative"],
        "draft_speculative": draft_stats["speculative"],
        "off_vs_pr6_tokens_per_s": round(
            tps(off_report) / max(tps(pr6_report), 1e-9), 3),
        "ngram_vs_off_tokens_per_s": round(
            tps(ngram_report) / max(tps(off_report), 1e-9), 3),
        "draft_vs_off_tokens_per_s": round(
            tps(draft_report) / max(tps(off_report), 1e-9), 3),
        "ngram_tokens_per_s_win": bool(
            tps(ngram_report) > tps(off_report)),
        "accepted_per_round_gt_1_5": True,  # asserted above
        "completions_identical": True,  # asserted above
        "invariants_checked_every_step": True,  # check_invariants=True
    }


def bench_serving_trace(dev, on_tpu):
    """Request-tracing leg (manifest v23): the disaggregated fleet
    under `--spec-decode ngram` with request tracing ON vs the
    identical traced-OFF twin (docs/OBSERVABILITY.md "Request
    tracing").  A repetitive multi-page workload (migrate side of the
    dispatcher's cost model, n-gram-draftable continuations) plus a
    sub-page mix (guaranteed re-prefill side) runs through both
    twins; the leg asserts greedy completions TOKEN-IDENTICAL (the
    tracer must be a pure observer), every completed request's
    trace_id resolving to exactly ONE connected trace tree (no
    orphan spans — kv_adopt joins via the FFKV frame header), a
    `migration` child present on every tree whose dispatch span
    priced `migrate`, spec verify rounds riding shared batch spans,
    and tracing overhead within 5% tokens/s on TPU captures (the CPU
    smoke bounds it loosely — tiny runs are noise-dominated)."""
    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models.transformer import build_gpt
    from flexflow_tpu.obs.metrics import MetricsRegistry
    from flexflow_tpu.obs.reqtrace import ReqTracer
    from flexflow_tpu.serving import DisaggServingFront
    from flexflow_tpu.serving.loadgen import (
        run_loadgen, sample_repetitive_workload, sample_workload)
    from tools import trace_analyze

    leg = MANIFEST["legs"]["serving_trace"]
    if on_tpu:
        vocab, max_seq = leg["vocab"], leg["max_seq"]
        hidden, layers, heads = leg["hidden"], leg["layers"], leg["heads"]
        inter, slots = leg["intermediate"], leg["slots"]
        page, n_req = leg["kv_page_size"], leg["requests"]
        rate, chunk = leg["offered_rps"], leg["prefill_chunk"]
        spec_k = leg["spec_k"]
        n_tpl, ppt = leg["num_templates"], leg["phrases_per_template"]
        phrase_len = leg["phrase_len"]
        phrases_range = tuple(leg["prompt_phrases_range"])
        mnt_range = tuple(leg["max_new_range"])
        n_sub = leg["subpage_requests"]
        sub_range = tuple(leg["subpage_len_range"])
        sample = leg["trace_sample"]
    else:
        vocab, max_seq = 64, 64
        hidden, layers, heads, inter = 64, 2, 4, 128
        slots, page, n_req, rate, chunk = 4, 4, 16, 400.0, 4
        spec_k = 4
        n_tpl, ppt, phrase_len = 2, 2, 8
        phrases_range, mnt_range = (3, 5), (2, 6)
        n_sub, sub_range = 6, (2, 4)
        sample = 1.0

    cfg = FFConfig(batch_size=slots, num_devices=1,
                   serving_slots=slots, kv_page_size=page,
                   serving_replicas=2, prefill_chunk=chunk,
                   spec_decode="ngram", spec_k=spec_k,
                   trace_sample=sample)
    ff = FFModel(cfg)
    build_gpt(ff, batch_size=slots, seq_length=max_seq,
              hidden_size=hidden, num_layers=layers, num_heads=heads,
              intermediate_size=inter, vocab_size=vocab)
    ff.compile(optimizer=SGDOptimizer(lr=0.5),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=[dev])
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (slots, max_seq)).astype(np.int32)
    pos = np.broadcast_to(np.arange(max_seq, dtype=np.int32),
                          (slots, max_seq)).copy()
    ff.train_step({"input": ids, "positions": pos}, ids)  # real weights

    wl_rng = np.random.RandomState(47)
    # multi-page repetitive prompts: migrate-side AND n-gram-draftable
    rep_wl, _ = sample_repetitive_workload(
        wl_rng, n_req, vocab, num_templates=n_tpl,
        phrases_per_template=ppt, phrase_len=phrase_len,
        prompt_phrases_range=phrases_range, max_new_range=mnt_range)
    sub_wl = sample_workload(wl_rng, n_sub, vocab,
                             prompt_len_range=sub_range,
                             max_new_range=mnt_range)
    workload = rep_wl + sub_wl

    def run_front(tracer, reg):
        front = DisaggServingFront.from_trained(
            ff, num_replicas=2, devices=[dev],
            roles=["prefill", "decode"], registry=reg,
            reqtrace=tracer)
        try:
            warm = [front.generate_async([1, 2], 2)
                    for _ in range(2 * slots)]
            warm.append(front.generate_async(
                list(range(1, 2 * page + 2)), 2))
            for h in warm:
                h.wait(300.0)
            report = run_loadgen(front, workload, rate, seed=29,
                                 detail=True, record_tokens=True)
            return report, front.stats()
        finally:
            front.close()

    off_report, _ = run_front(None, None)
    reg = MetricsRegistry()
    tracer = ReqTracer(registry=reg, sample=sample)
    on_report, on_stats = run_front(tracer, reg)

    # the tracer is a pure observer: greedy completions identical
    def by_idx(report):
        return {r["idx"]: r["tokens"] for r in report["records"]
                if r.get("ok")}
    off_toks, on_toks = by_idx(off_report), by_idx(on_report)
    assert set(off_toks) == set(on_toks), "completion sets differ"
    bad = sum(1 for i in off_toks if off_toks[i] != on_toks[i])
    assert bad == 0, f"{bad} completions differ traced vs untraced"

    dg = on_stats["disagg"]
    assert dg["migrate_decisions"] > 0, "no migration was ever chosen"
    assert dg["reprefill_decisions"] > 0, \
        "no re-prefill was ever chosen (sub-page mix missing?)"
    # every completed request = exactly one connected trace tree; the
    # warm-up traces drain through the same analyzer
    traces, batch = trace_analyze.build_traces(tracer.spans)
    ok_records = [r for r in on_report["records"] if r.get("ok")]
    assert all("trace_id" in r for r in ok_records), \
        "a completed request's detail record has no trace_id"
    disconnected, missing_migration = [], []
    for r in ok_records:
        spans = traces.get(r["trace_id"])
        assert spans, f"no trace tree for {r['trace_id']}"
        ok, orphans = trace_analyze.check_connected(spans)
        if not ok:
            disconnected.append((r["trace_id"], orphans))
        names = {s["name"] for s in spans}
        migrated = any(s["name"] == "dispatch"
                       and s["args"].get("decision") == "migrate"
                       for s in spans)
        if migrated and "migration" not in names:
            missing_migration.append(r["trace_id"])
    assert not disconnected, f"disconnected trees: {disconnected}"
    assert not missing_migration, \
        f"migrate decision but no migration span: {missing_migration}"
    # spec verify rounds ride shared batch spans the decode spans ref
    n_spec_batch = sum(1 for b in batch.values()
                       if b["name"] == trace_analyze.SPEC_VERIFY_SPAN)
    spec_rounds = sum(
        s["args"].get("spec_rounds", 0)
        for spans in traces.values() for s in spans
        if s["name"] == "decode")
    assert n_spec_batch > 0, "no spec_verify batch spans recorded"

    def tps(rep):
        return rep.get("tokens_per_s", 0.0)

    for r in off_report, on_report:
        r.pop("records", None)
    ratio = tps(on_report) / max(tps(off_report), 1e-9)
    # the headline overhead bar on TPU captures; the CPU smoke's tiny
    # run is noise-dominated, so it only sanity-bounds the ratio
    floor = 0.95 if on_tpu else 0.5
    assert ratio >= floor, \
        f"tracing overhead too high: tokens/s ratio {ratio:.3f}"
    return {
        "workload": (
            f"{n_req} repetitive reqs ({n_tpl} templates x {ppt} "
            f"phrases x {phrase_len} tokens, {phrases_range} "
            f"phrases/prompt) + {n_sub} sub-page reqs {sub_range}, "
            f"max_new {mnt_range}, Poisson {rate} rps, greedy, page "
            f"{page}, chunk {chunk}, ngram k {spec_k}; "
            f"prefill=1,decode=1, traced (sample {sample}) vs untraced"
        ),
        "untraced": off_report,
        "traced": on_report,
        "traced_vs_untraced_tokens_per_s": round(ratio, 3),
        "trace_stats": tracer.stats(),
        "traces_connected": len(ok_records),
        "spec_verify_batch_spans": n_spec_batch,
        "spec_rounds": spec_rounds,
        "decisions": {
            "migrate": dg["migrate_decisions"],
            "reprefill": dg["reprefill_decisions"],
            "migrations_ok": dg["migrations_ok"],
            "migrations_failed": dg["migrations_failed"],
        },
        "completions_identical": True,   # asserted above
        "one_tree_per_request": True,    # asserted above
        "migration_children_present": True,  # asserted above
        "overhead_within_bar": True,     # asserted above
    }


class _FrameDumpFabric:
    """KVTransferFabric wrapper that tees every FFKV frame to a file
    so tools/kvframe_fsck.py can audit the exact bytes that crossed
    the fabric — the bench's offline-verifier leg."""

    def __init__(self, inner, dump_dir):
        self.inner = inner
        self.kind = inner.kind + "+dump"
        self.dump_dir = dump_dir
        self.frames = 0

    def transfer(self, key, data):
        import os as _os
        self.frames += 1
        path = _os.path.join(self.dump_dir,
                             f"frame{self.frames:04d}.ffkv")
        with open(path, "wb") as f:
            f.write(data)
        return self.inner.transfer(key, data)

    def stats(self):
        out = dict(self.inner.stats())
        out["frames_dumped"] = self.frames
        return out


def bench_serving_handoff(dev, on_tpu):
    """Resumable-decode-handoff leg (manifest v24): a long generation
    is pinned mid-decode on one replica of a colocated 2-replica
    ServingFront, then that replica is DRAINED — with `--serving-
    handoff` ON vs OFF (docs/SERVING.md "Mid-decode handoff").  OFF
    is the baseline semantics: drain waits the generation out, so the
    undisturbed completion doubles as the byte-identity oracle.  ON
    must pause the sequence at a step boundary, stream its KV blocks
    (prompt + generated, partial tail included) to the surviving
    replica as FFKV frames, resume mid-generation, and retire the
    source WITHOUT waiting out the generation — asserted as: the
    source retired while the long request was still running, every
    completion byte-identical to the OFF run, zero handoff faults,
    and >0 bytes/blocks streamed.  Every frame that crossed the
    fabric is teed to disk and tools/kvframe_fsck.py must pass over
    the dump (exit 0).  Reports drain wall-time both modes, migrated
    bytes/blocks, and the full handoff decision counters."""
    import shutil
    import tempfile

    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models.transformer import build_gpt
    from flexflow_tpu.obs.metrics import MetricsRegistry
    from flexflow_tpu.serving import ServingFront
    from flexflow_tpu.serving.kv_transfer import (InProcessFabric,
                                                  KVMigrator)
    from flexflow_tpu.serving.loadgen import sample_workload
    from tools import kvframe_fsck

    leg = MANIFEST["legs"]["serving_handoff"]
    if on_tpu:
        vocab, max_seq = leg["vocab"], leg["max_seq"]
        hidden, layers, heads = leg["hidden"], leg["layers"], leg["heads"]
        inter, slots = leg["intermediate"], leg["slots"]
        page, chunk = leg["kv_page_size"], leg["prefill_chunk"]
        n_bg = leg["background_requests"]
        bg_range = tuple(leg["background_len_range"])
        bg_mnt = tuple(leg["background_max_new_range"])
        long_len, long_mnt = leg["long_prompt_len"], leg["long_max_new"]
    else:
        vocab, max_seq = 64, 64
        hidden, layers, heads, inter = 64, 2, 4, 128
        slots, page, chunk = 4, 4, 4
        n_bg, bg_range, bg_mnt = 6, (2, 6), (2, 6)
        long_len, long_mnt = 8, 40

    cfg = FFConfig(batch_size=slots, num_devices=1,
                   serving_slots=slots, kv_page_size=page,
                   serving_replicas=2, prefill_chunk=chunk)
    ff = FFModel(cfg)
    build_gpt(ff, batch_size=slots, seq_length=max_seq,
              hidden_size=hidden, num_layers=layers, num_heads=heads,
              intermediate_size=inter, vocab_size=vocab)
    ff.compile(optimizer=SGDOptimizer(lr=0.5),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=[dev])
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (slots, max_seq)).astype(np.int32)
    pos = np.broadcast_to(np.arange(max_seq, dtype=np.int32),
                          (slots, max_seq)).copy()
    ff.train_step({"input": ids, "positions": pos}, ids)  # real weights

    wl_rng = np.random.RandomState(53)
    bg_wl = sample_workload(wl_rng, n_bg, vocab,
                            prompt_len_range=bg_range,
                            max_new_range=bg_mnt)
    long_prompt = [int(t) for t in
                   wl_rng.randint(1, vocab, long_len)]

    def run(handoff, dump_dir=None):
        reg = MetricsRegistry()
        front = ServingFront.from_trained(ff, num_replicas=2,
                                          devices=[dev], registry=reg,
                                          handoff=handoff)
        fabric = None
        if dump_dir is not None:
            # pre-seat the lazy handoff migrator on a frame-dumping
            # fabric so every streamed block lands on disk for fsck
            fabric = _FrameDumpFabric(InProcessFabric(), dump_dir)
            front._handoff_mig = KVMigrator(
                fabric, registry=reg, logger=front.log)
        try:
            warm = [front.generate_async([1, 2], 2)
                    for _ in range(2 * slots)]
            for h in warm:
                h.wait(300.0)
            bg = [front.generate_async(p, m) for p, m in bg_wl]
            bg_toks = [h.wait(300.0) for h in bg]

            bases = {id(r): r.scheduler.stats()["tokens_generated"]
                     for r in front.replicas if r.alive}
            h_long = front.generate_async(long_prompt, long_mnt)
            holder, deadline = None, time.monotonic() + 60.0
            while time.monotonic() < deadline:
                for r in front.replicas:
                    if not r.alive or r.outstanding == 0:
                        continue
                    done = (r.scheduler.stats()["tokens_generated"]
                            - bases.get(id(r), 0))
                    if done >= 2:  # provably mid-decode, not prefill
                        holder = r
                        break
                if holder is not None or h_long.event.is_set():
                    break
                time.sleep(0.0005)
            assert holder is not None, \
                "long generation finished before it could be pinned"

            t0 = time.monotonic()
            assert front.drain_replica(holder), "drain refused"
            long_done_at_retire = None
            deadline = time.monotonic() + 120.0
            while time.monotonic() < deadline:
                if holder.state == "retired":
                    long_done_at_retire = h_long.event.is_set()
                    break
                time.sleep(0.0005)
            drain_s = time.monotonic() - t0
            assert long_done_at_retire is not None, "drain never retired"
            long_toks = h_long.wait(300.0)

            st = front.stats()
            return {
                "long_tokens": long_toks,
                "bg_tokens": bg_toks,
                "drain_s": round(drain_s, 4),
                "long_done_at_retire": long_done_at_retire,
                "handoff": st.get("handoff"),
                "paused": reg.counter("serving/handoff_paused").value,
                "resumed": reg.counter("serving/handoff_resumed").value,
                "frames_dumped": fabric.frames if fabric else 0,
            }
        finally:
            front.close()

    off = run(False)
    dump_dir = tempfile.mkdtemp(prefix="ffkv_bench_")
    try:
        on = run(True, dump_dir=dump_dir)

        # OFF is the oracle: drain waited the generation out untouched
        assert off["long_done_at_retire"], \
            "baseline drain retired before the generation completed"
        assert off["paused"] == 0 and off["handoff"] is None

        # ON retired the source mid-generation and streamed the state
        assert not on["long_done_at_retire"], \
            "handoff drain waited out the generation"
        assert on["paused"] >= 1 and on["resumed"] >= 1, \
            f"no pause/resume: {on['paused']}/{on['resumed']}"
        ho = on["handoff"]
        assert ho and ho["ok"] >= 1, f"no successful handoff: {ho}"
        assert not ho["faults"], f"handoff faults fired: {ho['faults']}"
        kvt = ho.get("kv_transfer") or {}
        assert kvt.get("bytes_streamed", 0) > 0, f"no bytes moved: {kvt}"
        assert kvt.get("blocks_streamed", 0) > 0

        # byte-identity: pause/stream/resume is invisible in the output
        assert on["long_tokens"] == off["long_tokens"], \
            "handed-off long generation diverged from the oracle"
        assert on["bg_tokens"] == off["bg_tokens"], \
            "background completions diverged"

        # offline audit of the exact frames that crossed the fabric
        assert on["frames_dumped"] >= 1, "no FFKV frames dumped"
        fsck_rc = kvframe_fsck.main([dump_dir])
        assert fsck_rc == 0, f"kvframe_fsck found problems (rc {fsck_rc})"
    finally:
        shutil.rmtree(dump_dir, ignore_errors=True)

    if on_tpu:
        assert on["drain_s"] < off["drain_s"], \
            "handoff drain was not faster than waiting out the generation"
    for rep in (on, off):
        rep.pop("long_tokens", None)
        rep.pop("bg_tokens", None)
    return {
        "workload": (
            f"{n_bg} background reqs {bg_range} + one pinned "
            f"{long_len}-token prompt x {long_mnt} new tokens, greedy, "
            f"page {page}, chunk {chunk}; drain the holder, "
            f"--serving-handoff on vs off (colocated 2-replica)"
        ),
        "handoff_on": on,
        "handoff_off": off,
        "drain_speedup": round(
            off["drain_s"] / max(on["drain_s"], 1e-9), 2),
        "migrated": {
            "bytes": (on["handoff"] or {}).get(
                "kv_transfer", {}).get("bytes_streamed", 0),
            "blocks": (on["handoff"] or {}).get(
                "kv_transfer", {}).get("blocks_streamed", 0),
        },
        "decisions": {
            "requested": on["handoff"]["requested"],
            "ok": on["handoff"]["ok"],
            "replays": on["handoff"]["replays"],
            "migrate": on["handoff"]["migrate_decisions"],
            "replay": on["handoff"]["replay_decisions"],
        },
        "completions_identical": True,   # asserted above
        "retired_mid_generation": True,  # asserted above
        "kvframe_fsck_clean": True,      # asserted above
    }


def bench_autoscale(dev, on_tpu):
    """Autoscaling-front leg (manifest v15): a SEEDED square-wave
    burst trace against a ServingFront that starts at min_replicas
    with a ServingAutoscaler attached (serving/autoscaler.py).  The
    burst must scale the fleet UP (replicas spawned through the warm
    from_trained factory) and the post-burst calm must DRAIN it back
    down gracefully — in-flight slots run to completion, so
    requeued_requests stays 0 and a post-run token-identity audit
    (greedy re-generation of every completion on the settled fleet)
    must match byte-for-byte.  Availability acceptance is >= 0.99.
    The autoscaler tick history carries the replica-count timeline;
    TTFT records bucket into a per-second p99 timeline."""
    import time as _time

    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models.transformer import build_gpt
    from flexflow_tpu.obs.metrics import MetricsRegistry
    from flexflow_tpu.serving import ServingAutoscaler, ServingFront
    from flexflow_tpu.serving.loadgen import run_loadgen, sample_workload

    leg = MANIFEST["legs"]["autoscale"]
    if on_tpu:
        vocab, max_seq = leg["vocab"], leg["max_seq"]
        hidden, layers, heads = leg["hidden"], leg["layers"], leg["heads"]
        inter, slots = leg["intermediate"], leg["slots"]
        page, n_req = leg["kv_page_size"], leg["requests"]
        calm_rps, burst = leg["calm_rps"], leg["burst_factor"]
        period_s = leg["period_s"]
        plen_range = tuple(leg["prompt_len_range"])
        mnt_range = tuple(leg["max_new_range"])
    else:
        vocab, max_seq = 64, 64
        hidden, layers, heads, inter = 128, 2, 4, 256
        slots, page, n_req = 4, 8, 96
        # the burst must OUTRUN one replica's measured service rate on
        # CPU (~100-150 req/s at these lengths) or nothing scales
        calm_rps, burst, period_s = 40.0, 12.0, 0.5
        plen_range, mnt_range = (2, 8), (8, 24)
    min_r, max_r = leg["min_replicas"], leg["max_replicas"]

    cfg = FFConfig(batch_size=slots, num_devices=1,
                   serving_slots=slots, kv_page_size=page,
                   serving_replicas=min_r,
                   serving_min_replicas=min_r,
                   serving_max_replicas=max_r)
    ff = FFModel(cfg)
    build_gpt(ff, batch_size=slots, seq_length=max_seq,
              hidden_size=hidden, num_layers=layers, num_heads=heads,
              intermediate_size=inter, vocab_size=vocab)
    ff.compile(optimizer=SGDOptimizer(lr=0.5),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=[dev])
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (slots, max_seq)).astype(np.int32)
    pos = np.broadcast_to(np.arange(max_seq, dtype=np.int32),
                          (slots, max_seq)).copy()
    ff.train_step({"input": ids, "positions": pos}, ids)  # real weights

    reg = MetricsRegistry()
    front = ServingFront.from_trained(ff, num_replicas=min_r,
                                      devices=[dev], registry=reg)
    scaler = ServingAutoscaler(
        front, min_r, max_r,
        interval_s=leg["interval_s"], cooldown_s=leg["cooldown_s"],
        queue_high=leg["queue_high"], queue_low=leg["queue_low"],
        drain_timeout_s=leg["drain_timeout_s"], registry=reg,
    )
    try:
        # warm the initial replica's decode compile before timing
        warm = [front.generate_async([1, 2], 2) for _ in range(slots)]
        for h in warm:
            h.wait(300.0)
        scaler.start()
        wl_rng = np.random.RandomState(11)
        workload = sample_workload(wl_rng, n_req, vocab,
                                   prompt_len_range=plen_range,
                                   max_new_range=mnt_range)
        t0 = _time.monotonic()
        report = run_loadgen(front, workload, calm_rps, seed=7,
                             detail=True, record_tokens=True,
                             arrival="square", burst_factor=burst,
                             period_s=period_s)
        def fleet_size():
            with front._cv:
                return len(front.replicas)

        # a scale-up decided near the end of the trace may still be
        # compiling (add_replica appends AFTER the build) — wait for
        # it to land before judging the drain-down
        # list() snapshot: the loop thread is still appending ticks
        max_fleet = max((e["replicas"] for e in list(scaler.history)),
                        default=min_r)
        # wait on the PEAK fleet, not the current one: when the trace
        # outlasts both the scale-up and the drain-down, the fleet is
        # already back at min_r and a current-size check would spin to
        # the full deadline
        spin_deadline = _time.monotonic() + 120.0
        while (_time.monotonic() < spin_deadline
               and (scaler._spawning
                    or (scaler.scale_ups > 0 and max_fleet <= min_r))):
            _time.sleep(0.05)
            max_fleet = max(max_fleet, fleet_size())
        # post-burst calm: the loop must drain back to min_replicas
        drain_deadline = _time.monotonic() + 120.0
        while _time.monotonic() < drain_deadline:
            max_fleet = max(max_fleet, fleet_size())
            if fleet_size() <= min_r and scaler._draining is None:
                break
            _time.sleep(0.05)
        scaler.stop()
        final_fleet = fleet_size()
        # token-identity audit: greedy decode is deterministic, so
        # every completion re-generated on the settled fleet must be
        # byte-identical — a drain that disturbed an in-flight slot
        # (or a requeue that lost prefix state) would show here
        records = report.pop("records", [])
        audited = mismatches = 0
        for r in records:
            if not r.get("ok") or "tokens" not in r:
                continue
            p, mnt = workload[r["idx"]]
            audited += 1
            if front.generate(p, mnt, timeout=120.0) != r["tokens"]:
                mismatches += 1
        availability = report["completed"] / max(report["requests"], 1)
        # p99-TTFT timeline: 1s submit-time buckets over the run
        buckets = {}
        for r in records:
            if r.get("ok") and "ttft_s" in r:
                buckets.setdefault(int(r["submit_s"]), []).append(
                    r["ttft_s"])
        ttft_timeline = [
            {"t_s": t, "n": len(v),
             "p99_ms": round(float(np.percentile(v, 99)) * 1e3, 2)}
            for t, v in sorted(buckets.items())
        ]
        # replica-count timeline from the autoscaler's tick history
        # (downsampled: keep every entry where the fleet size changed,
        # plus scale decisions)
        timeline = []
        last = None
        for e in scaler.history:
            if e["replicas"] != last or e["action"] != "hold":
                timeline.append({"t_s": round(e["t"] - t0, 2),
                                 "replicas": e["replicas"],
                                 "action": e["action"]})
                last = e["replicas"]
        return {
            "workload": (
                f"{n_req} reqs, square-wave {calm_rps}->"
                f"{calm_rps * burst} rps every {period_s}s, fleet "
                f"[{min_r}, {max_r}] starting at {min_r}"
            ),
            "availability": round(availability, 4),
            "completed": report["completed"],
            "submitted": report["requests"],
            "scale_ups": scaler.scale_ups,
            "scale_downs": scaler.scale_downs,
            "forced_retires": scaler.forced_retires,
            "max_fleet": max_fleet,
            "final_fleet": final_fleet,
            "scaled_up_on_burst": bool(scaler.scale_ups >= 1),
            "drained_down_after": bool(final_fleet == min_r
                                       and scaler.scale_downs >= 1),
            "requeued_requests": front.requeued_requests,
            "token_identity": {
                "audited": audited,
                "mismatches": mismatches,
                "identical": bool(audited > 0 and mismatches == 0),
            },
            "replica_timeline": timeline,
            "ttft_p99_timeline_ms": ttft_timeline,
            "tokens_per_s": report.get("tokens_per_s", 0.0),
        }
    finally:
        front.close()


def main():
    import gc

    import jax

    # a device that cannot be initialised raises here: a stack trace
    # and a non-zero exit, never a result line
    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"

    bert = bench_bert(dev, on_tpu)
    gc.collect()  # drop the previous leg's weights/opt state from HBM
    resnet = bench_resnet50(dev, on_tpu)
    gc.collect()
    bert_long = bench_bert_long(dev, on_tpu)
    gc.collect()
    dlrm = bench_dlrm(dev, on_tpu)
    gc.collect()
    moe = bench_moe_dispatch(dev, on_tpu)
    gc.collect()
    wu = bench_weight_update(on_tpu)
    gc.collect()
    ladder = bench_zero_ladder(dev, on_tpu)
    gc.collect()
    ckpt = bench_checkpoint(dev, on_tpu)
    gc.collect()
    serving = bench_serving(dev, on_tpu)
    gc.collect()
    serving_prefix = bench_serving_prefix(dev, on_tpu)
    gc.collect()
    serving_paged_kernel = bench_serving_paged_kernel(dev, on_tpu)
    gc.collect()
    serving_gspmd = bench_serving_gspmd(dev, on_tpu)
    gc.collect()
    serving_resilience = bench_serving_resilience(dev, on_tpu)
    gc.collect()
    serving_disagg = bench_serving_disagg(dev, on_tpu)
    gc.collect()
    serving_spec = bench_serving_spec(dev, on_tpu)
    gc.collect()
    serving_trace = bench_serving_trace(dev, on_tpu)
    gc.collect()
    serving_handoff = bench_serving_handoff(dev, on_tpu)
    gc.collect()
    autoscale = bench_autoscale(dev, on_tpu)
    gc.collect()
    cold_start = bench_cold_start(dev, on_tpu)
    gc.collect()
    host_loss = bench_host_loss(dev, on_tpu)
    gc.collect()
    multi_slice = bench_multi_slice(dev, on_tpu)
    gc.collect()
    long_context = bench_long_context(dev, on_tpu)
    geomean = float(np.sqrt(max(bert["vs_a100"], 1e-9)
                            * max(resnet["vs_a100"], 1e-9)))
    result = {
        # value is the BERT leg's samples/s (round-over-round
        # comparable); vs_baseline is the geomean of BOTH headline
        # legs' vs-A100 ratios; per-leg numbers live under "legs"
        "metric": (
            "samples/sec/chip, BERT-base seq128 b64 token-ids bf16 "
            "(vs_baseline = geomean of bert_base+resnet50 legs vs A100)"
            if on_tpu else "CPU smoke: BERT tiny + ResNet tiny"
        ),
        "value": bert["samples_per_sec_per_chip"],
        "unit": "samples/s",
        "vs_baseline": round(geomean, 4) if on_tpu else 0.0,
        "manifest_version": MANIFEST["version"],
        "legs": {"bert_base": bert, "resnet50": resnet,
                 "bert_long_context": bert_long, "dlrm": dlrm,
                 "moe_dispatch": moe, "weight_update": wu,
                 "zero_ladder": ladder,
                 "checkpoint": ckpt, "serving": serving,
                 "serving_prefix": serving_prefix,
                 "serving_paged_kernel": serving_paged_kernel,
                 "serving_gspmd": serving_gspmd,
                 "serving_resilience": serving_resilience,
                 "serving_disagg": serving_disagg,
                 "serving_spec": serving_spec,
                 "serving_trace": serving_trace,
                 "serving_handoff": serving_handoff,
                 "autoscale": autoscale,
                 "cold_start": cold_start, "host_loss": host_loss,
                 "multi_slice": multi_slice,
                 "long_context": long_context},
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
