"""Request-scoped distributed tracing (obs/reqtrace.py,
docs/OBSERVABILITY.md "Request tracing"): tracer/context units
(sampling, the name-keyed open-span registry, wire adoption, Chrome
export, overflow), the E2E contract on a disaggregated fake-KV fleet
(a migrated request = ONE connected trace tree whose kv_adopt span
lands on the decode replica's track), speculative verify batch spans,
exemplar-linked SLO histograms + the Prometheus /metrics endpoint,
the zero-allocation disabled path, the cumulative-snapshot drain
contract, and the trace_analyze / telemetry_summary tools."""
import importlib
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from flexflow_tpu.obs.metrics import MetricsRegistry, to_prometheus
from flexflow_tpu.obs.reqtrace import (FRONT_PID, NULL_REQTRACER,
                                       ReqTracer)
from flexflow_tpu.obs.reqtrace import span_allocations
from flexflow_tpu.obs.trace import Tracer, span
from flexflow_tpu.serving import DisaggServingFront
from flexflow_tpu.serving.scheduler import ContinuousScheduler
from flexflow_tpu.serving.server import serve_http

ta = importlib.import_module("tools.trace_analyze")
summary = importlib.import_module("tools.telemetry_summary")

V = 16
NO_SLEEP = lambda s: None  # noqa: E731


def span_recs(reg):
    return [r for r in reg.drain() if r.get("kind") == "span"]


# -- tracer / context units ----------------------------------------------

def test_sampling_bounds_and_null_tracer():
    assert ReqTracer(sample=0.0).trace() is None
    assert ReqTracer(sample=1.0).trace() is not None
    with pytest.raises(ValueError, match="sample"):
        ReqTracer(sample=1.5)
    assert NULL_REQTRACER.trace() is None
    assert NULL_REQTRACER.begin_remote({"trace_id": "x"}, "kv") is None
    assert NULL_REQTRACER.enabled is False and not NULL_REQTRACER.sample


def test_partial_sampling_is_deterministic_per_seed():
    tr = ReqTracer(sample=0.5, seed=7)
    kept = sum(tr.trace() is not None for _ in range(200))
    assert 60 < kept < 140              # ~binomial(200, .5)
    assert tr.traces_started == kept    # rejected ones never count


def test_span_tree_schema_and_connectivity():
    reg = MetricsRegistry()
    tr = ReqTracer(registry=reg)
    ctx = tr.trace("request", prompt_len=3)
    ctx.begin("queue", depth=0)
    ctx.end("queue")
    ctx.begin("dispatch", replica=0)
    ctx.end("dispatch")
    ctx.finish(ok=True)
    recs = span_recs(reg)
    assert [r["name"] for r in recs] == ["queue", "dispatch", "request"]
    root = recs[-1]
    assert root["trace_id"] == "req-000001"
    assert root["parent_id"] is None and root["pid"] == FRONT_PID
    assert root["args"] == {"prompt_len": 3, "ok": True}
    for child in recs[:2]:
        assert child["parent_id"] == root["span_id"]
        assert child["dur_us"] >= 0
    traces, batch = ta.build_traces(recs)
    assert not batch
    ok, orphans = ta.check_connected(traces["req-000001"])
    assert ok and not orphans


def test_rebegin_truncates_and_finish_force_ends():
    reg = MetricsRegistry()
    tr = ReqTracer(registry=reg)
    ctx = tr.trace()
    ctx.begin("queue")
    ctx.begin("queue", requeued=True)   # stale one ends truncated
    ctx.begin("dispatch")               # never explicitly ended
    ctx.finish(ok=False)
    recs = span_recs(reg)
    by_name = {}
    for r in recs:
        by_name.setdefault(r["name"], []).append(r)
    assert by_name["queue"][0]["args"]["truncated"] is True
    assert len(by_name["queue"]) == 2
    assert len(by_name["dispatch"]) == 1  # force-ended exactly once
    ok, _ = ta.check_connected(recs)
    assert ok


def test_annotate_open_id_and_end_are_name_safe():
    tr = ReqTracer()
    ctx = tr.trace()
    ctx.annotate("nope", x=1)           # no such open span: no-op
    ctx.end("nope")
    assert ctx.open_id("nope") is None
    span = ctx.begin("dispatch")
    ctx.annotate("dispatch", decision="migrate")
    assert ctx.open_id("dispatch") == span.span_id
    ctx.end("dispatch")
    assert span.args["decision"] == "migrate"


def test_wire_round_trips_and_begin_remote_joins_tree():
    tr = ReqTracer()
    ctx = tr.trace()
    mig = ctx.begin("migration")
    wire = json.loads(json.dumps(ctx.wire(parent=mig.span_id, pid=1)))
    adopted = tr.begin_remote(wire, "kv_adopt", blocks=2)
    adopted.end(ok=True)
    assert adopted.trace_id == ctx.trace_id
    assert adopted.parent_id == mig.span_id
    assert adopted.pid == 1
    assert tr.begin_remote(None, "kv_adopt") is None
    assert tr.begin_remote({"parent": 3}, "kv_adopt") is None


def test_shared_dispatch_span_chrome_export_through_the_single_writer(
        tmp_path):
    """The scheduler's per-dispatch host span is recorded once for the
    sampled requests that rode it; the run's one writer (`Tracer.write`)
    merges the request tracks into the ring's dump."""
    writer = Tracer(run_id="r0")
    tr = ReqTracer()
    ctx = tr.trace()
    with span("sched.decode.dispatch", rows=2) as dispatch:
        pass
    tr.shared_span(dispatch, pid=2)
    ctx.finish(ok=True)
    events = tr.chrome_events()
    x = [e for e in events if e["ph"] == "X"]
    meta = [e for e in events if e["ph"] == "M"]
    assert {e["name"] for e in x} == {"sched.decode.dispatch", "request"}
    batch_ev = next(e for e in x if e["name"] == "sched.decode.dispatch")
    assert batch_ev["pid"] == 2 and "trace_id" not in batch_ev["args"]
    assert batch_ev["args"]["span_id"] == dispatch.span_id
    assert batch_ev["args"]["rows"] == 2
    assert {e["args"]["name"] for e in meta} == \
        {"serving front", "serving replica 2"}
    path = tmp_path / "trace.json"
    writer.write(str(path), extra_events=events)
    doc = json.loads(path.read_text())
    assert doc["otherData"]["run_id"] == "r0"
    got = doc["traceEvents"]
    ts = [e["ts"] for e in got]
    assert ts == sorted(ts)
    # the ring's copy of the dispatch span and the request tracks, on
    # one clock: the request began before the dispatch and ended after
    ring_ev = next(e for e in got if e.get("cat") == "span"
                   and e["args"]["span_id"] == dispatch.span_id)
    req_ev = next(e for e in got if e["name"] == "request")
    assert req_ev["ts"] <= ring_ev["ts"]
    assert ring_ev["ts"] + ring_ev["dur"] <= req_ev["ts"] + req_ev["dur"]
    assert len([e for e in got if e.get("cat") != "span"]) == len(events)


def test_span_overflow_drops_not_grows():
    tr = ReqTracer(max_spans=2)
    ctx = tr.trace()
    for i in range(3):
        ctx.begin(f"s{i}")
        ctx.end(f"s{i}")
    st = tr.stats()
    assert st["spans_recorded"] == 2 and st["spans_dropped"] == 1


# -- E2E: disaggregated fleet --------------------------------------------

class FakeKVModel:
    """tests/test_serving_disagg.py's deterministic next-token model
    with the exportable KV surface: token t emits (t+1) % V."""

    def __init__(self, batch_slots=2, max_seq=32, page_size=4):
        self.batch_slots = batch_slots
        self.max_seq = max_seq
        self.page_size = page_size
        self.max_blocks_per_seq = max_seq // page_size
        self.num_blocks = 1 + batch_slots * self.max_blocks_per_seq
        self.vocab = V
        self.kv = np.zeros((self.num_blocks, page_size, 2), np.float32)

    def reset(self):
        pass

    def step(self, tokens, seq_lens, block_tables):
        logits = np.zeros((self.batch_slots, V), np.float32)
        nxt = (np.asarray(tokens) + 1) % V
        logits[np.arange(self.batch_slots), nxt] = 1.0
        return logits

    def export_block(self, block):
        return {"kv": np.array(self.kv[block])}

    def import_block(self, block, arrays):
        self.kv[block] = arrays["kv"]


def expected(prompt, mnt):
    out = list(prompt)
    t = prompt[-1]
    for _ in range(mnt):
        t = (t + 1) % V
        out.append(t)
    return out


def factory(rid, survivors=None):
    return FakeKVModel()


def test_disagg_migrated_request_is_one_connected_tree():
    """THE acceptance criterion: a request the dispatcher diverts
    through the prefill class yields exactly one connected trace tree
    covering queue/dispatch (cost terms)/migration/kv_adopt (on the
    DECODE replica's track, via the FFKV frame header)/prefill/decode
    — plus a re-prefilled request whose tree has no migration child."""
    reg = MetricsRegistry()
    tracer = ReqTracer(registry=reg)
    front = DisaggServingFront(factory, num_replicas=2,
                               roles=["prefill", "decode"],
                               registry=reg, reqtrace=tracer,
                               sleep=NO_SLEEP)
    reqs = [([1, 2, 3, 4, 5, 6, 7, 8], 4), ([5], 3)]
    try:
        hs = [front.generate_async(p, m) for p, m in reqs]
        outs = [h.wait(30.0) for h in hs]
    finally:
        front.close()
    for (p, m), got in zip(reqs, outs):
        assert got == expected(p, m)
    assert hs[0].migration["decision"] == "migrate"
    assert hs[1].migration["decision"] == "reprefill"  # sub-page

    recs = span_recs(reg)
    traces, batch = ta.build_traces(recs)
    assert len(traces) == len(reqs)           # sample=1.0: all traced
    for h in hs:
        assert h.trace is not None
        ok, orphans = ta.check_connected(traces[h.trace.trace_id])
        assert ok, f"orphans: {orphans}"

    mig = traces[hs[0].trace.trace_id]
    names = {s["name"] for s in mig}
    assert {"request", "queue", "dispatch", "migration", "kv_adopt",
            "prefill", "decode"} <= names
    # the priced decision rides the dispatch span
    disp = next(s for s in mig if s["name"] == "dispatch"
                and "decision" in s["args"])
    assert disp["args"]["decision"] == "migrate"
    assert disp["args"]["migrate_s"] < disp["args"]["reprefill_s"]
    # the adopt span crossed the fabric onto the decode replica (id 1)
    adopt = next(s for s in mig if s["name"] == "kv_adopt")
    assert adopt["pid"] == 1
    assert adopt["args"]["ok"] is True and adopt["args"]["blocks"] > 0
    mig_span = next(s for s in mig if s["name"] == "migration")
    assert adopt["parent_id"] == mig_span["span_id"]
    assert mig_span["args"]["ok"] is True
    # root completion accounting
    root = next(s for s in mig if s["parent_id"] is None)
    assert root["args"]["ok"] is True
    assert root["args"]["n_generated"] == reqs[0][1]
    # phase spans reference shared batch spans instead of owning them
    dec = next(s for s in mig if s["name"] == "decode")
    refs = dec["args"]["batch_spans"]
    assert refs and all(batch[r]["trace_id"] is None for r in refs)

    # no migration child on the re-prefilled request's tree
    assert "migration" not in {
        s["name"] for s in traces[hs[1].trace.trace_id]}

    # the analyzer agrees end-to-end
    report = ta.analyze(recs)
    assert report["traces"] == 2 and not report["disconnected"]
    assert report["phases"]["decode"]["traces"] == 2
    assert report["phases"]["migration"]["traces"] == 1


def test_untraced_fleet_has_no_spans_and_no_allocations():
    reg = MetricsRegistry()
    front = DisaggServingFront(factory, num_replicas=2,
                               roles=["prefill", "decode"],
                               registry=reg, sleep=NO_SLEEP)
    try:
        before = span_allocations()
        h = front.generate_async([1, 2, 3, 4, 5, 6, 7, 8], 4)
        assert h.wait(30.0) == expected([1, 2, 3, 4, 5, 6, 7, 8], 4)
        assert span_allocations() == before   # zero-cost disabled path
        assert h.trace is None
    finally:
        front.close()
    assert not span_recs(reg)


# -- speculative verify rounds -------------------------------------------

class FakeSpecModel(FakeKVModel):
    """FakeKVModel plus the verify surface (same successor rule), so
    the n-gram drafter's chains are always accepted."""

    def __init__(self, spec_k=4, **kw):
        super().__init__(max_seq=64, **kw)
        self.prefix_cache = True
        self.spec_decode = "ngram"
        self.spec_k = spec_k
        self.verify_chunk = spec_k + 1

    def verify_step(self, tokens, seq_lens, counts, block_tables):
        C = tokens.shape[1]
        logits = np.zeros((self.batch_slots, C, V), np.float32)
        nxt = (np.asarray(tokens) + 1) % V
        for j in range(C):
            logits[np.arange(self.batch_slots), j, nxt[:, j]] = 1.0
        return logits


def test_spec_verify_rounds_ride_shared_batch_spans():
    reg = MetricsRegistry()
    tracer = ReqTracer(registry=reg)
    sched = ContinuousScheduler(FakeSpecModel(), registry=reg,
                                reqtrace=tracer, trace_pid=3)
    try:
        ctx = tracer.trace("request")
        prompt = [(3 + i) % V for i in range(V + 2)]
        h = sched.generate_async(prompt, 20, trace=ctx)
        assert h.wait(30.0) == expected(prompt, 20)
        ctx.finish(ok=True)
    finally:
        sched.close()
    recs = span_recs(reg)
    traces, batch = ta.build_traces(recs)
    spans = traces[ctx.trace_id]
    dec = next(s for s in spans if s["name"] == "decode")
    assert dec["pid"] == 3
    assert dec["args"]["spec_rounds"] > 0
    assert dec["args"]["spec_accepted"] == dec["args"]["spec_proposed"] > 0
    verify = [batch[r] for r in dec["args"]["batch_spans"]
              if batch[r]["name"] == ta.SPEC_VERIFY_SPAN]
    assert verify
    assert all(v["args"]["proposer"] == "NGramProposer" for v in verify)
    # the analyzer buckets referenced verify time into spec_verify
    phases = ta.phase_breakdown(spans, batch)
    assert phases.get("spec_verify", 0.0) > 0.0


# -- exemplars, cumulative drains, /metrics ------------------------------

def test_slo_histograms_carry_worst_sample_exemplar():
    reg = MetricsRegistry()
    tracer = ReqTracer(registry=reg)
    front = DisaggServingFront(factory, num_replicas=2,
                               roles=["prefill", "decode"],
                               registry=reg, reqtrace=tracer,
                               sleep=NO_SLEEP)
    try:
        h = front.generate_async([1, 2, 3, 4, 5], 4)
        assert h.wait(30.0) == expected([1, 2, 3, 4, 5], 4)
    finally:
        front.close()
    recs = reg.drain()
    lat = [r for r in recs if r["kind"] == "histogram"
           and r["name"] == "serving/request_latency_ms"]
    assert lat and lat[-1]["exemplar"]["trace_id"] == h.trace.trace_id
    assert lat[-1]["exemplar"]["value"] > 0
    # exemplar resets at drain; count/sum stay cumulative snapshots
    again = [r for r in reg.drain() if r["kind"] == "histogram"
             and r["name"] == "serving/request_latency_ms"]
    assert again and "exemplar" not in again[-1]
    assert again[-1]["count"] == lat[-1]["count"]
    assert again[-1]["sum"] == lat[-1]["sum"]


def test_cumulative_drain_monotone_and_summary_dedupes():
    """The drain contract the doc promises: metric records are
    cumulative snapshots — a second flush re-appends current values,
    never resets — and telemetry_summary keeps the latest per name."""
    reg = MetricsRegistry()
    reg.counter("serving/requests_done").inc(2)
    reg.histogram("serving/ttft_ms").observe(5.0)
    first = {(r["name"]): r for r in reg.drain()
             if r["kind"] in ("counter", "histogram")}
    reg.counter("serving/requests_done").inc(3)
    reg.histogram("serving/ttft_ms").observe(7.0)
    second = {(r["name"]): r for r in reg.drain()
              if r["kind"] in ("counter", "histogram")}
    assert second["serving/requests_done"]["value"] == 5 > \
        first["serving/requests_done"]["value"]
    h1, h2 = first["serving/ttft_ms"], second["serving/ttft_ms"]
    assert h2["count"] == 2 > h1["count"]
    assert h2["sum"] == pytest.approx(12.0) and h2["sum"] > h1["sum"]
    # summarize sees both generations of records; latest must win
    recs = list(first.values()) + list(second.values())
    text = summary.summarize(recs)
    assert "5" in text  # requests_done reflects the later snapshot


def test_metrics_endpoint_serves_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("serving/requests_done").inc(4)
    reg.gauge("serving/queue_depth").set(1.0)
    reg.histogram("serving/ttft_ms").observe(812.4,
                                             exemplar="req-000042")
    sched = ContinuousScheduler(FakeKVModel(), registry=reg)
    server = serve_http(generator=sched, port=0, block=False,
                        registry=reg)
    port = server.server_address[1]
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            ctype = r.headers["Content-Type"]
            body = r.read().decode()
        assert ctype.startswith("text/plain; version=0.0.4")
        assert "# TYPE serving_requests_done counter" in body
        assert "serving_requests_done 4" in body
        assert "serving_queue_depth 1.0" in body
        assert "# TYPE serving_ttft_ms summary" in body
        assert "serving_ttft_ms_sum" in body
        # OpenMetrics exemplar annotation on the _count sample
        assert ('serving_ttft_ms_count 1 # {trace_id="req-000042"} '
                "812.4") in body
    finally:
        server.shutdown()
        sched.close()
    # every line parses as `name value [exemplar]` or a comment
    for line in body.splitlines():
        if not line or line.startswith("#"):
            continue
        name, value = line.split(" # ")[0].rsplit(" ", 1)
        float(value)
        assert "/" not in name  # sanitized for Prometheus


def test_metrics_endpoint_404_without_registry():
    sched = ContinuousScheduler(FakeKVModel())
    server = serve_http(generator=sched, port=0, block=False)
    port = server.server_address[1]
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10)
        assert ei.value.code == 404
    finally:
        server.shutdown()
        sched.close()


def test_to_prometheus_unit():
    reg = MetricsRegistry()
    reg.histogram("serving/per_token_ms").observe(3.0)
    text = to_prometheus(reg)
    assert "# TYPE serving_per_token_ms summary" in text
    assert "serving_per_token_ms_count 1" in text
    assert "#" not in text.split("serving_per_token_ms_count 1")[1] \
        .splitlines()[0]  # no exemplar without one


# -- tools: trace_analyze CLI, telemetry_summary torn tails --------------

def _write_jsonl(path, recs, torn=None, torn_at=None):
    lines = [json.dumps(r) for r in recs]
    if torn is not None:
        lines.insert(len(lines) if torn_at is None else torn_at, torn)
    path.write_text("\n".join(lines) + "\n")


def make_trace_recs():
    reg = MetricsRegistry()
    tr = ReqTracer(registry=reg)
    for mnt in (3, 1):
        ctx = tr.trace("request")
        ctx.begin("queue")
        ctx.end("queue")
        ctx.begin("decode")
        ctx.end("decode")
        ctx.finish(ok=True, n_generated=mnt)
    return span_recs(reg)


def test_trace_analyze_cli_slowest_and_check(tmp_path, capsys):
    recs = make_trace_recs()
    path = tmp_path / "run_telemetry.jsonl"
    _write_jsonl(path, recs, torn='{"kind":')   # tolerated here
    assert ta.main([str(tmp_path), "--slowest", "1"]) == 0
    out = capsys.readouterr().out
    assert "Request traces: 2" in out
    assert "Slowest 1:" in out and "req-00000" in out
    assert ta.main([str(path), "--check"]) == 0

    # orphan a span: --check exits 2, plain run stays 0
    bad = [dict(r) for r in recs]
    for r in bad:
        if r["name"] == "queue" and r["trace_id"] == "req-000001":
            r["parent_id"] = 999999
    _write_jsonl(path, bad)
    assert ta.main([str(path)]) == 0
    assert ta.main([str(path), "--check"]) == 2
    assert "DISCONNECTED" in capsys.readouterr().out
    assert ta.main([str(tmp_path / "nope.jsonl")]) == 1


def test_telemetry_summary_tracing_section(tmp_path, capsys):
    path = tmp_path / "run_telemetry.jsonl"
    _write_jsonl(path, make_trace_recs())
    assert summary.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "Tracing" in out
    assert "traces recorded" in out and "slowest" in out


def test_telemetry_summary_rejects_mid_file_corruption(tmp_path,
                                                       capsys):
    path = tmp_path / "run_telemetry.jsonl"
    _write_jsonl(path, make_trace_recs(), torn="{garbage", torn_at=2)
    assert summary.main([str(path)]) == 1
    err = capsys.readouterr().err
    assert "[3]" in err and "mid-file" in err
    # mid-file corruption is NOT a torn tail: the escape hatch refuses
    assert summary.main([str(path), "--allow-torn-tail"]) == 1


def test_telemetry_summary_torn_tail_escape_hatch(tmp_path, capsys):
    path = tmp_path / "run_telemetry.jsonl"
    _write_jsonl(path, make_trace_recs(), torn='{"kind": "spa')  # tail
    assert summary.main([str(path)]) == 1
    assert "--allow-torn-tail" in capsys.readouterr().err
    assert summary.main([str(path), "--allow-torn-tail"]) == 0
    cap = capsys.readouterr()
    assert "Tracing" in cap.out
    assert "torn tail" in cap.err  # tolerated, but still called out
