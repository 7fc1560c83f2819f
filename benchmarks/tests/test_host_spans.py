"""benchmarks/host_spans.py on a small xplane recorded on a TPU v5e
(PR 26: `tools/record_host_spans.py`, the toy GPT-2 server answering
three requests with the Python tracer off), whose host plane carries
the program's spans beside the device planes; and a CPU rehearsal that
prints every new per-layer metric whose source is not the device, from
`toy-spans.BENCHMARK.json` (the toy file plus PR 26's entries)."""
import os

import pytest

from benchmarks import host_spans as hs
from benchmarks import reduce_trace as rt
from conftest import ROOT, result_line, run_cell

RECORDED = os.path.join(os.path.dirname(__file__),
                        "recorded_spans.xplane.pb")
SPANS = os.path.join(ROOT, "benchmarks", "tests", "toy-spans.BENCHMARK.json")


@pytest.fixture(scope="module")
def recorded():
    host = hs.read_host(RECORDED)
    planes = [p for p in rt.read_planes(RECORDED) if p["ops"]]
    assert planes, "the recording holds no device plane"
    return hs.dispatch_line(host), planes[0], rt.reduce(RECORDED, 1)


def test_recorded_host_plane_carries_the_programs_spans(recorded):
    line, _, _ = recorded
    names = {s.name for s in line}
    assert {"sched.iteration", "sched.admit", "sched.prefill.dispatch",
            "sched.decode.dispatch", "sched.sample", "model.enqueue",
            "model.fetch"} <= names
    decode = next(s for s in line if s.name == "sched.decode.dispatch")
    assert decode.stats["slots"] == 4
    assert all(a.end_s >= a.start_s for a in line)


def test_host_and_device_planes_share_a_clock_to_a_millisecond(recorded):
    """The toy programs run 20 and 100 microseconds, so this recording
    shows how far the two planes' clocks disagree: a program appears up
    to 0.52 ms BEFORE the host span that enqueued it.  Within a
    millisecond of slack every program starts between its own host
    dispatch span's start and the next one's, and ends before that
    dispatch span does (the toy decode program is over before the host
    gets to `model.fetch`; cell 3's 39 ms program ends inside it)."""
    slack = 1e-3
    line, _, summary = recorded
    decodes = sorted(summary["modules"]["jit_step"])
    prefills = sorted(summary["modules"]["jit_prefill"])
    n, late = hs.starts_in_order(line, "sched.decode.dispatch", decodes,
                                 slack)
    assert n >= 6 and late == 0
    n, late = hs.starts_in_order(line, "sched.prefill.dispatch", prefills,
                                 slack)
    assert n >= 2 and late == 0
    # the skew itself: the first prefill program "starts" before the
    # host span that enqueued it
    first = next(s for s in line if s.name == "sched.prefill.dispatch")
    assert -slack < prefills[0][0] - first.start_s < 0
    hosts = [s for s in line if s.name == "sched.decode.dispatch"]
    runs = [r for r in decodes if r[0] >= hosts[0].start_s - slack]
    for h, (start, end, _) in zip(hosts, runs):
        assert h.start_s - slack <= start and end <= h.end_s + slack
    # the least shift that restores causality finds that skew, and with
    # it applied no slack is needed
    shift = hs.causal_shift_s(line, summary["modules"], hs.DISPATCHES,
                              "jit_step")
    assert 0.0003 < shift < slack
    moved = [(a + shift, b + shift, c) for a, b, c in prefills]
    assert hs.starts_in_order(line, "sched.prefill.dispatch", moved) == (
        len(moved), 0)
    # the arithmetic of the tails on what this trace holds
    tails = hs.fetch_tails(line, decodes)
    assert len(tails) == len(decodes)
    assert all(0 <= tail < 0.01 for tail, ok in tails if ok)


def test_idle_by_host_span_accounts_for_every_idle_second(recorded):
    line, plane, summary = recorded
    split = hs.idle_by_host_span(line, plane["ops"])
    idle = summary["window_s"] - summary["busy_s"]
    assert sum(split.values()) == pytest.approx(idle, rel=1e-6)
    assert all(v >= 0 for v in split.values())
    # a toy server's device idles while the host works: inside the
    # program's spans, not outside them
    assert split.get(hs.OUTSIDE, 0.0) < 0.5 * idle
    assert any(k.startswith("sched.") for k in split)


def test_rehearsal_prints_every_new_metric_that_needs_no_device():
    train = ["--benchmark", SPANS, "--rehearse-cpu", "--workload",
             "toy-bert.toy-train", "--seed", "5", "--seconds", "1",
             "--trace", "1"]
    rc, out, err = run_cell(train)
    assert rc == 0, err[-2000:]
    got = set(result_line(out)["metrics"])
    assert {"step.host_ms", "step.dispatch_wait_ms", "compile.passes_s",
            "compile.init_weights_inner_s"} <= got
    assert not {m for m in got if m.startswith("idle.")}
    assert "step.host_ms: " in out and "outside it" in out

    serve = ["--benchmark", SPANS, "--rehearse-cpu", "--workload",
             "toy-gpt2.toy-serve", "--seed", "7", "--seconds", "3",
             "--trace", "1"]
    rc, out, err = run_cell(serve)
    assert rc == 0, err[-2000:]
    line = result_line(out)
    assert {"sched.self_ms.capacity", "sched.admit_ms.capacity",
            "sched.sample_ms.capacity", "decode.rows.capacity",
            "prefill.useful_share.capacity", "compile.passes_s",
            "compile.init_weights_inner_s", "serve.build_twin_s",
            "serve.lazy_compile_s"} <= set(line["metrics"])
    assert "decode.fetch_tail_ms.capacity" not in line["metrics"]
    assert "idle.unattributed_share.capacity" not in line["metrics"]
    assert 0 < line["metrics"]["decode.rows.capacity"]["value"] <= 4
    assert 0 < line["metrics"]["prefill.useful_share.capacity"][
        "value"] <= 100
