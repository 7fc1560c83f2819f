"""The one span primitive (flexflow_tpu/obs/trace.py) and its two sinks:
the bounded ring (parents per thread, self time) and the jax profiler's
host plane, read back with the benchmark's own reader
(benchmarks/host_spans.py) around a toy scheduler run and a toy
`train_step` loop; and that reader's arithmetic on made-up timelines."""
import glob
import os
import threading
import time

import jax
import numpy as np
import pytest

from benchmarks import host_spans as hs
from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.obs import trace
from flexflow_tpu.obs.trace import SpanRecord, self_time, span, spans


# -- the ring ---------------------------------------------------------------

def test_ring_is_bounded_after_ten_times_its_length():
    for i in range(10 * trace.RING_SIZE):
        with span("filler"):
            pass
    records = spans()
    assert len(records) == trace.RING_SIZE
    ids = [r.span_id for r in records]
    assert ids == sorted(ids) and len(set(ids)) == len(ids)


def test_parents_come_from_each_threads_own_stack():
    barrier = threading.Barrier(2, timeout=10)
    tag = f"t{time.monotonic_ns()}"

    def work(who):
        with span(f"{tag}.outer", who=who):
            barrier.wait()          # both outers are open at once
            with span(f"{tag}.inner", who=who):
                barrier.wait()
            barrier.wait()

    threads = [threading.Thread(target=work, args=(w,)) for w in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
        assert not t.is_alive()
    mine = [r for r in spans() if r.name.startswith(tag)]
    outer = {r.args["who"]: r for r in mine if r.name.endswith("outer")}
    inner = {r.args["who"]: r for r in mine if r.name.endswith("inner")}
    assert set(outer) == set(inner) == {0, 1}
    for who in (0, 1):
        assert outer[who].parent_id is None
        assert inner[who].parent_id == outer[who].span_id
        assert inner[who].thread == outer[who].thread
        assert outer[who].t_start <= inner[who].t_start
        assert inner[who].t_end <= outer[who].t_end
    assert outer[0].thread != outer[1].thread


def test_self_time_on_a_hand_made_tree():
    def rec(sid, parent, t0, t1):
        return SpanRecord(sid, parent, f"s{sid}", 1, t0, t1, {})

    tree = [
        rec(1, None, 0.0, 10.0),
        rec(2, 1, 1.0, 4.0),      # child
        rec(3, 1, 3.0, 6.0),      # overlaps its sibling by 1
        rec(4, 2, 1.5, 2.0),      # grandchild: not 1's to subtract
        rec(5, 99, 7.0, 8.0),     # parent not among the records
    ]
    got = self_time(tree)
    assert got[1] == pytest.approx(10.0 - 5.0)   # union of [1,4] and [3,6]
    assert got[2] == pytest.approx(3.0 - 0.5)
    assert got[3] == pytest.approx(3.0)
    assert got[4] == pytest.approx(0.5)
    assert got[5] == pytest.approx(1.0)


def test_a_span_costs_microseconds_not_more():
    """The contract says under 2 microseconds on a quiet machine; this
    guard is loose enough for a loaded test worker and still catches a
    span that starts doing real work (a lock, a file, a syscall)."""
    n = 20_000
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for i in range(n):
            with span("cost", i=i):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 20e-6, f"{best * 1e9:.0f} ns a span"


# -- the profiler's host plane, read back by the benchmark's reader ---------

def traced(tmp_path, work):
    """Run ``work()`` under a profiler session; the program's spans per
    thread line, as the benchmark reads them."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    return hs.read_host(path)


def inside(child, parent):
    return parent.start_s <= child.start_s and child.end_s <= parent.end_s


def children_of(parent, spans_):
    """Direct children by containment on one thread's line."""
    within = [s for s in spans_ if s is not parent and inside(s, parent)]
    return [s for s in within
            if not any(o is not s and inside(s, o) for o in within)]


def test_train_step_spans_on_the_host_plane(tmp_path, devices8):
    cfg = FFConfig(batch_size=8, num_devices=1)
    ff = FFModel(cfg)
    x = ff.create_tensor([8, 16], name="input")
    ff.dense(ff.relu(ff.dense(x, 32)), 4)
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=devices8[:1])
    rng = np.random.RandomState(0)
    X = rng.randn(8, 16).astype(np.float32)
    y = rng.randint(0, 4, 8).astype(np.int32)

    def work():
        for _ in range(3):
            m = ff.train_step({"input": X}, y)
        jax.block_until_ready(m["loss"])

    line = hs.dispatch_line(traced(tmp_path, work))
    steps = [s for s in line if s.name == "train_step"]
    assert [s.stats["step"] for s in steps] == [0, 1, 2]
    assert [s.stats["first"] for s in steps] == [1, 0, 0]
    for s in steps:
        assert [c.name for c in children_of(s, line)] == [
            "host_transfer", "train_step.rng_split", "train_step.dispatch",
            "train_step.caches"]
    # the ring holds the same spans, parents by id
    ring_steps = [r for r in spans() if r.name == "train_step"][-3:]
    kids = hs.children(spans())
    for r in ring_steps:
        assert [k.name for k in kids[r.span_id]] == [
            "host_transfer", "train_step.rng_split", "train_step.dispatch",
            "train_step.caches"]


@pytest.fixture(scope="module")
def tiny_server(devices8):
    from flexflow_tpu.models.transformer import build_gpt
    from flexflow_tpu.serving import build_front

    ff = FFModel(FFConfig(batch_size=1, num_devices=1, serving_slots=2,
                          kv_page_size=4, prefill_chunk=4))
    build_gpt(ff, batch_size=1, seq_length=32, hidden_size=16,
              num_layers=1, num_heads=2, intermediate_size=32, vocab_size=16)
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=devices8[:1])
    front = build_front(ff)
    try:
        # every step program once, so the traced requests compile nothing
        front.generate_async(list(range(1, 11)), 3, 0.0).wait(120.0)
        yield front
    finally:
        front.close(10.0)


def test_scheduler_spans_on_the_host_plane(tmp_path, tiny_server):
    front = tiny_server

    def work():
        hs_ = [front.generate_async(list(range(1, n)), 4, 0.0)
               for n in (12, 7)]
        for h in hs_:
            h.wait(60.0)
        time.sleep(0.15)    # three whole idle turns (a park is 0.05 s)

    line = hs.dispatch_line(traced(tmp_path, work))
    by_name = {}
    for s in line:
        by_name.setdefault(s.name, []).append(s)
    # every span of the scheduler's row, and the model's under the dispatches
    for name in ("sched.iteration", "sched.services", "sched.admit",
                 "sched.idle_wait", "sched.prefill.prepare",
                 "sched.prefill.dispatch", "sched.decode.prepare",
                 "sched.decode.dispatch", "sched.sample", "sched.observe",
                 "model.enqueue", "model.fetch"):
        assert by_name.get(name), f"no {name} span on the host plane"

    # a whole turn that did both dispatches: its children, in order
    turn = next(it for it in by_name["sched.iteration"]
                if {"sched.prefill.dispatch", "sched.decode.dispatch"}
                <= {c.name for c in children_of(it, line)})
    assert [c.name for c in children_of(turn, line)] == [
        "sched.services", "sched.admit", "sched.prefill.prepare",
        "sched.prefill.dispatch", "sched.decode.prepare",
        "sched.decode.dispatch", "sched.sample", "sched.observe"]
    prefill = next(c for c in children_of(turn, line)
                   if c.name == "sched.prefill.dispatch")
    assert [c.name for c in children_of(prefill, line)] == ["model.enqueue"]
    decode = next(c for c in children_of(turn, line)
                  if c.name == "sched.decode.dispatch")
    assert [c.name for c in children_of(decode, line)] == [
        "model.enqueue", "model.fetch"]
    assert children_of(decode, line)[0].stats["first"] == 0

    # the counts they carry
    assert prefill.stats["capacity"] == 2 * 4     # slots x chunk
    assert 1 <= prefill.stats["rows"] <= 2
    assert 1 <= prefill.stats["tokens"] <= prefill.stats["capacity"]
    assert decode.stats["slots"] == 2
    assert decode.stats["rows"] + decode.stats["feeding"] in (1, 2)
    admits = by_name["sched.admit"]
    assert sum(s.stats["admitted"] for s in admits) == 2
    assert all({"queue_depth", "wait_ms"} <= set(s.stats) for s in admits)
    samples = by_name["sched.sample"]
    assert sum(s.stats["tokens"] for s in samples) == 8      # 2 x 4 asked
    assert sum(s.stats["finished"] for s in samples) == 2
    # an idle turn is services, admit and the park on the queue
    idle = next(it for it in by_name["sched.iteration"]
                if any(c.name == "sched.idle_wait"
                       for c in children_of(it, line)))
    assert [c.name for c in children_of(idle, line)] == [
        "sched.services", "sched.admit", "sched.idle_wait"]

    # front.stats() carries what the driver used to reach past it for
    st = front.stats()
    assert st["admitted"] >= 3 and st["queue_wait_s_sum"] > 0
    pool = st["replicas"][0]["kv_pool"]
    assert pool["peak_used_blocks"] >= 3 and pool["page_size"] == 4


def test_build_front_spans_in_the_ring(tiny_server):
    records = spans()
    (front_span,) = hs.named(records, "serve.build_front")[-1:]
    kids = hs.children(records)
    (twin,) = [k for k in kids[front_span.span_id]
               if k.name == "serve.build_twin"]
    assert front_span.args == {"replicas": 1, "slots": 2}
    assert twin.args["replica"] == 0 and twin.args["slots"] == 2
    assert twin.args["pool_blocks"] == tiny_server.replicas[
        0].scheduler.model.num_blocks
    names = [k.name for k in kids[twin.span_id]]
    assert "compile" in names and "serve.copy_weights" in names
    # the twin's compile is not a top-level one
    twin_compile = next(k for k in kids[twin.span_id] if k.name == "compile")
    assert twin_compile.parent_id == twin.span_id
    assert {k.name for k in kids[twin_compile.span_id]} >= {
        "compile.passes", "init_weights", "compile.opt_state",
        "build_step_fns"}
    # the programs' lazy compiles are flagged on their first call only
    firsts = [r for r in hs.named(records, "model.enqueue")
              if r.args["first"]]
    assert 2 <= len(firsts) <= 3


# -- the reader's arithmetic --------------------------------------------------

def S(name, a, b, **stats):
    return hs.HostSpan(name, a, b, stats)


def test_idle_by_innermost_host_span_on_a_made_up_timeline():
    line = [S("sched.iteration", 0.0, 10.0),
            S("sched.admit", 1.0, 2.0),
            S("sched.decode.dispatch", 3.0, 8.0),
            S("model.enqueue", 3.0, 4.0),
            S("model.fetch", 4.0, 8.0),
            S("sched.iteration", 12.0, 14.0)]
    assert hs.innermost_segments(line) == [
        (0.0, 1.0, "sched.iteration"), (1.0, 2.0, "sched.admit"),
        (2.0, 3.0, "sched.iteration"), (3.0, 4.0, "model.enqueue"),
        (4.0, 8.0, "model.fetch"), (8.0, 10.0, "sched.iteration"),
        (12.0, 14.0, "sched.iteration")]
    ops = [("a", -1.0, 0.5), ("b", 3.5, 5.0), ("c", 4.5, 9.0),
           ("d", 13.0, 13.5)]
    split = hs.idle_by_host_span(line, ops)
    # gaps: [0.5, 3.5] and [9, 13]
    assert split == pytest.approx({
        "sched.iteration": 0.5 + 1.0 + 1.0 + 1.0, "sched.admit": 1.0,
        "model.enqueue": 0.5, hs.OUTSIDE: 2.0})
    assert sum(split.values()) == pytest.approx(3.0 + 4.0)
    assert hs.idle_by_host_span([], ops) == pytest.approx({hs.OUTSIDE: 7.0})


def test_fetch_tails_and_start_order_on_a_made_up_timeline():
    line = [S("sched.decode.dispatch", 0.0, 5.0), S("model.fetch", 1.0, 5.0),
            S("sched.decode.dispatch", 6.0, 9.0), S("model.fetch", 7.0, 9.0)]
    runs = [(0.5, 4.8, 4.0), (6.5, 8.9, 2.0), (9.5, 11.0, 1.0)]
    tails = hs.fetch_tails(line, runs)
    assert tails[0] == (pytest.approx(0.2), True)
    assert tails[1] == (pytest.approx(0.1), True)
    assert tails[2] == (None, False)      # cut off by the stretch's end
    assert hs.starts_in_order(line, "sched.decode.dispatch", runs) == (2, 0)
    # a program the stretch's opening cut from its host span is left out
    cut = [(-3.0, -1.0, 1.0)] + runs[:2]
    assert hs.starts_in_order(line, "sched.decode.dispatch", cut) == (2, 0)
    # a device clock that runs 5.8 s late shows: a program starts after
    # the NEXT host span did
    late = [(s + 5.8, e + 5.8, b) for s, e, b in runs[:2]]
    assert hs.starts_in_order(line, "sched.decode.dispatch", late)[1] >= 1
    assert hs.starts_in_order(line, "sched.decode.dispatch", late,
                              slack_s=6.0)[1] == 0
    assert hs.dispatch_line({"a#0": [S("sched.admit", 0, 1)],
                             "b#1": line}) == line
    assert hs.dispatch_line({"a#0": [S("sched.admit", 0, 1)]}) == []


def test_causal_shift_restores_a_skewed_device_clock():
    period = 0.27
    line, prefills, decodes = [], [], []
    for k in range(5):
        t = k * period
        line += [S("sched.prefill.dispatch", t, t + 0.002),
                 S("sched.decode.dispatch", t + 0.003, t + 0.268),
                 S("model.fetch", t + 0.006, t + 0.268)]
        prefills.append((t + 0.001, t + 0.226, 0.2))      # lag 1 ms
        decodes.append((t + 0.226, t + 0.266, 0.04))      # tail 2 ms
    line.sort(key=lambda s: (s.start_s, -s.end_s))

    def skewed(by):
        return {"jit_prefill": [(a + by, b + by, c) for a, b, c in prefills],
                "jit_step": [(a + by, b + by, c) for a, b, c in decodes]}

    def shift(by):
        return hs.causal_shift_s(line, skewed(by), hs.DISPATCHES, "jit_step")

    assert hs.nearest_lags(line, "sched.prefill.dispatch",
                           prefills) == pytest.approx([0.001] * 5)
    assert shift(0.0) == 0.0
    assert shift(0.0005) == 0.0            # inside what causality allows
    # the device clock 1.5 ms early: programs start 0.5 ms before their
    # host spans; the least shift puts the earliest at its span's start
    assert shift(-0.0015) == pytest.approx(0.0005)
    # 3 ms late: programs end 1 ms after the fetch that waited for them
    assert shift(0.003) == pytest.approx(-0.001)
    # a dispatch cut from its host span by the stretch's edge is left out
    assert len(hs.nearest_lags(line, "sched.prefill.dispatch",
                               prefills + [(5 * period + 0.1, 9.0, 0.2)])) == 5
