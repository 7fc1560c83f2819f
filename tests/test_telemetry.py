"""Unified run telemetry (flexflow_tpu/obs/): trace-event schema,
metrics-registry semantics, named_scope HLO attribution, fidelity
records, and what a run without a trace_dir leaves behind."""
import json
import logging
import os

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.obs import (
    MetricsRegistry,
    RunTelemetry,
    parse_profile_steps,
)
from flexflow_tpu.obs import trace
from flexflow_tpu.obs.metrics import emit_counters


def _build_mlp(cfg, in_dim=32, classes=10):
    ff = FFModel(cfg)
    x = ff.create_tensor([cfg.batch_size, in_dim], name="input")
    h = ff.dense(x, 64)
    h = ff.relu(h)
    ff.dense(h, classes)
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


def _data(n=64, in_dim=32, classes=10):
    rng = np.random.RandomState(0)
    return (rng.randn(n, in_dim).astype(np.float32),
            rng.randint(0, classes, n).astype(np.int32))


def _span_durations(events):
    """(name, dur) of the run's spans in a trace.json: complete "X"
    events of the ring's dump, each with a span id and non-negative
    duration; a child names a parent that is in the document."""
    spans = [e for e in events if e.get("cat") == "span"]
    ids = {e["args"]["span_id"] for e in spans}
    assert len(ids) == len(spans)
    for e in spans:
        assert e["ph"] == "X" and e["dur"] >= 0
    named = {e["args"]["span_id"]: e for e in spans}
    for e in spans:
        parent = named.get(e["args"].get("parent_id"))
        if parent is not None:
            assert parent["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + 1e-3
    return [(e["name"], e["dur"]) for e in spans]


# ---------------------------------------------------------------------------
# tentpole: trace-event timeline + JSONL + fidelity from an 8-device fit
# ---------------------------------------------------------------------------

def test_fit_trace_and_telemetry_8dev(tmp_path, devices8):
    """Acceptance: an 8-device CPU-mesh fit with --trace-dir produces a
    loadable Chrome trace (>= one span per step, plus compile spans) and
    a run_telemetry.jsonl with unified metrics + a fidelity record."""
    td = str(tmp_path / "telem")
    cfg = FFConfig(batch_size=16, num_devices=8, trace_dir=td)
    ff = _build_mlp(cfg)
    X, y = _data(64)
    ff.fit(X, y, batch_size=16, epochs=2, verbose=False)

    with open(os.path.join(td, "trace.json")) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert events
    ts = [e["ts"] for e in events]
    assert ts == sorted(ts)  # serialized sorted by timestamp
    names = [n for n, _ in _span_durations(events)]
    # 4 batches/epoch x 2 epochs; one train_step with its children each,
    # and one wait on the loader a batch plus the one that ends an epoch
    assert names.count("train_step") == 8
    assert names.count("host_transfer") == 8
    assert names.count("train_step.dispatch") == 8
    assert names.count("fit.dataloader_wait") == 10
    assert names.count("device_drain") == 2
    assert "compile" in names
    assert "init_weights" in names  # the eager XLA compile inside compile()

    recs = [json.loads(line)
            for line in open(os.path.join(td, "run_telemetry.jsonl"))]
    assert all(r["schema"] == 1 for r in recs)
    by_kind = {}
    for r in recs:
        by_kind.setdefault(r["kind"], []).append(r)
    hists = {r["name"]: r for r in by_kind["histogram"]}
    assert hists["fit/dispatch_ms"]["count"] == 8
    gauges = {r["name"]: r for r in by_kind["gauge"]}
    assert gauges["compile/total_ms"]["value"] > 0
    assert "fit/metrics/train_all" in gauges  # PerfMetrics unified
    (fid,) = by_kind["fidelity"]
    assert fid["predicted_step_ms"] > 0
    assert fid["measured_step_ms"] > 0
    assert fid["predicted_vs_measured"] == pytest.approx(
        fid["predicted_step_ms"] / fid["measured_step_ms"], abs=1e-4
    )  # record values are rounded to 4 decimals
    assert fid["mesh_axes"] == {"data": 8}
    assert fid["num_devices"] == 8
    assert fid["source"] == "fit"


def test_supervisor_emits_checkpoint_and_restart_spans(tmp_path, devices8):
    from flexflow_tpu.resilience import FaultKind, FaultPlan, TrainingSupervisor

    td = str(tmp_path / "telem")
    cfg = FFConfig(batch_size=8, num_devices=8, trace_dir=td,
                   checkpoint_every=2, max_restarts=3, retry_backoff=0.0)
    ff = _build_mlp(cfg)
    X, y = _data(32)
    sup = TrainingSupervisor(
        ff, str(tmp_path / "ckpt"),
        fault_plan=FaultPlan.single(3, FaultKind.STEP_EXCEPTION),
        sleep=lambda s: None,
    )
    report = sup.run(X, y, num_steps=4)
    assert report.counters["restarts"] == 1

    with open(os.path.join(td, "trace.json")) as f:
        events = json.load(f)["traceEvents"]
    names = [n for n, _ in _span_durations(events)]
    assert "checkpoint_write" in names
    assert "restart" in names
    recs = [json.loads(line)
            for line in open(os.path.join(td, "run_telemetry.jsonl"))]
    gauges = {r["name"]: r["value"] for r in recs if r["kind"] == "gauge"}
    # supervisor counters unified into the registry
    assert gauges["resilience/restarts"] == 1
    assert gauges["resilience/checkpoints"] >= 1
    # the supervisor's restore log line captured as an event record
    logs = [r for r in recs
            if r["kind"] == "event" and r["name"] == "log"]
    assert any("restored step" in r["fields"]["message"] for r in logs)


def test_crashed_fit_still_writes_artifacts(tmp_path, devices8):
    """A traced run that dies mid-training is exactly the run whose
    telemetry matters: fit's finally clause must flush the artifacts."""

    class Boom(Exception):
        pass

    class Crasher:
        def on_train_begin(self, ff):
            pass

        def on_epoch_end(self, ff, epoch, pm):
            raise Boom()

    td = str(tmp_path / "telem")
    cfg = FFConfig(batch_size=16, num_devices=8, trace_dir=td)
    ff = _build_mlp(cfg)
    X, y = _data(64)
    with pytest.raises(Boom):
        ff.fit(X, y, batch_size=16, epochs=2, verbose=False,
               callbacks=[Crasher()])
    with open(os.path.join(td, "trace.json")) as f:
        events = json.load(f)["traceEvents"]
    names = [n for n, _ in _span_durations(events)]
    assert names.count("train_step") == 4  # epoch 0's steps made it to disk
    assert os.path.exists(os.path.join(td, "run_telemetry.jsonl"))


# ---------------------------------------------------------------------------
# metrics registry semantics
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_histogram_semantics():
    reg = MetricsRegistry()
    c = reg.counter("c")
    c.inc()
    c.inc(4)
    assert reg.counter("c") is c and c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g")
    g.set(2.5)
    g.set(1.0)
    assert g.value == 1.0
    h = reg.histogram("h")
    for v in (1.0, 3.0, 2.0):
        h.observe(v)
    assert (h.count, h.sum, h.min, h.max) == (3, 6.0, 1.0, 3.0)
    assert h.mean == pytest.approx(2.0)
    with pytest.raises(TypeError):
        reg.gauge("c")  # same name, different type

    recs = {(r["kind"], r["name"]): r for r in reg.drain()}
    assert recs[("counter", "c")]["value"] == 5
    assert recs[("histogram", "h")]["mean"] == pytest.approx(2.0)
    assert all(r["schema"] == 1 and "ts" in r for r in recs.values())


def test_emit_counters_keeps_log_line_format(caplog):
    """The migrated call sites must emit the EXACT RecursiveLogger
    `label: k=v ...` line (float -> %.4g) while also folding into the
    registry."""
    from flexflow_tpu.logger import search_logger

    reg = MetricsRegistry()
    stats = {"evals": 12, "evals_per_sec": 123.4567, "flag": True}
    with caplog.at_level(logging.INFO, logger="flexflow_tpu.search"):
        emit_counters(search_logger, "mcmc eval stats", stats,
                      registry=reg, group="search/mcmc")
    assert caplog.messages == ["mcmc eval stats: evals=12 evals_per_sec=123.5 flag=True"]
    gauges = {r["name"]: r["value"] for r in reg.drain()
              if r["kind"] == "gauge"}
    assert gauges["search/mcmc/evals"] == 12
    assert gauges["search/mcmc/evals_per_sec"] == pytest.approx(123.4567)
    assert gauges["search/mcmc/flag"] == 1


def test_search_stats_reach_registry(devices8):
    cfg = FFConfig(batch_size=16, num_devices=2, telemetry=True,
                   search_budget=2, search_algo="mcmc",
                   search_calibrate=False)
    ff = _build_mlp(cfg)
    assert ff.strategy.search_stats  # dict API unchanged
    names = [r["name"] for r in ff.telemetry.metrics.drain()
             if r["kind"] == "gauge"]
    assert any(n.startswith("search/mcmc/") for n in names)
    assert "compile/search_ms" in names


def test_calib_logger_lands_in_telemetry():
    from flexflow_tpu.logger import calib_logger

    tel = RunTelemetry(enabled=True)
    try:
        calib_logger.info("region %s failed: %r", ["dense_0"], "boom")
        events = [r for r in tel.metrics.drain() if r["kind"] == "event"]
        assert any(
            r["fields"]["logger"] == "flexflow_tpu.calib"
            and "dense_0" in r["fields"]["message"]
            for r in events
        )
    finally:
        tel.close()


# ---------------------------------------------------------------------------
# device scopes: op kinds and names in the compiled step HLO
# ---------------------------------------------------------------------------

def test_named_scope_op_names_in_step_hlo():
    """Every PCG op of the step names its instructions `<Kind>:<name>`
    (obs/scopes.py), and `parse` reads both back from the compiled
    HLO's metadata; tests/test_device_scopes.py holds the grammar."""
    import re

    import jax

    from flexflow_tpu.obs.scopes import parse

    cfg = FFConfig(batch_size=8, num_devices=1)
    ff = _build_mlp(cfg)
    X, y = _data(8)
    put_inputs, put_labels = ff._device_put_batch({"input": X}, y)
    rng = jax.random.key(0)
    lowered = ff._step_fn.lower(
        ff._weights, ff._opt_state, ff._state, put_inputs, put_labels, rng
    )
    hlo = lowered.compile().as_text()
    found = {(s.kind, s.name)
             for s in map(parse, re.findall(r'op_name="([^"]*)"', hlo))}
    for op in ff.operators.topo_order():
        if op.name.startswith("dense"):
            assert (type(op).__name__, op.name) in found
    assert {("loss", None), ("optimizer", None)} <= found


# ---------------------------------------------------------------------------
# no trace_dir: spans are recorded all the same, nothing is written
# ---------------------------------------------------------------------------

def test_fit_without_trace_dir_writes_no_file_and_ring_stays_bounded(
        tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    cfg = FFConfig(batch_size=16, num_devices=1)
    ff = _build_mlp(cfg)
    assert not ff.telemetry.enabled and ff.telemetry.trace_dir is None
    X, y = _data(64)
    before = {r.span_id for r in trace.spans()}
    ff.fit(X, y, batch_size=16, epochs=2, verbose=False)
    mine = [r for r in trace.spans() if r.span_id not in before]
    # one code path: the spans exist whatever the configuration says
    assert sum(r.name == "train_step" for r in mine) == 8
    assert sum(r.name == "train_step.dispatch" for r in mine) == 8
    assert len(trace.spans()) <= trace.RING_SIZE
    assert ff.telemetry.flush() == {}
    assert list(tmp_path.iterdir()) == []  # no trace.json, no JSONL
    # the registry is in memory only; the renamed histogram counts steps
    assert ff.telemetry.metrics.histogram("fit/dispatch_ms").count == 8


# ---------------------------------------------------------------------------
# knobs
# ---------------------------------------------------------------------------

def test_cli_knobs(tmp_path):
    td = str(tmp_path / "t")
    cfg = FFConfig.from_args(
        ["--trace-dir", td, "--profile-steps", "3:2", "--telemetry"]
    )
    assert cfg.trace_dir == td
    assert cfg.telemetry is True
    assert cfg.profile_steps == "3:2"
    assert parse_profile_steps("3:2") == (3, 5)

    assert FFConfig.from_args([]).trace_dir is None

    with pytest.raises(ValueError):
        FFConfig(profile_steps="3:2")  # needs trace_dir
    with pytest.raises(ValueError):
        FFConfig(trace_dir=td, profile_steps="nope")
    with pytest.raises(ValueError):
        FFConfig(trace_dir=td, profile_steps="3:0")


def test_print_profile_total_excludes_unmeasured(capsys):
    from flexflow_tpu.profiler import print_profile

    rows = [
        {"name": "a", "type": "LINEAR", "fwd_ms": 1.5, "flops": 1e9},
        {"name": "b", "type": "CACHE", "fwd_ms": None, "flops": 0.0},
        {"name": "c", "type": "LINEAR", "fwd_ms": 0.5, "flops": 1e9},
    ]
    print_profile(rows)
    out = capsys.readouterr().out
    assert "2.000" in out  # 1.5 + 0.5, Nones excluded
    assert "(2 measured / 3 total ops, 1 excluded)" in out
