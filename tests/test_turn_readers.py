"""The twelve readers ISSUE 53 added (`benchmarks/readers/turn.py`,
`sampled.py`, on `benchmarks/sampling_dispatch.py`), which read the
serving step whichever program ran it:

* every reader on a recorded ring whose stretch holds (i) only decode
  dispatches, (ii) only sampling passes, (iii) both, and (iv) on no
  traced stretch at all (None);
* a ring whose `model.enqueue` / `model.fetch` do not say their
  program (the parent of PR 53): the `turn.*` readers leave their
  metric out and raise nothing;
* the xplane half (`turn.launch_lag_ms`, `turn.fetch_tail_ms`, the
  two-sided clock shift, the closure) on a small trace recorded on a
  TPU v5e with a toy LongCat-Flash twin on the pass
  (`benchmarks/tools/record_turn_spans.py`), and its arithmetic on
  hand-made planes.

Nothing here touches a chip: the ring is made by opening spans, the
trace is a file.
"""
import os
import time
import types

import pytest

from benchmarks import host_spans as hs
from benchmarks import reduce_trace as rt
from benchmarks import sampling_dispatch as sd
from benchmarks.run import load_module
from flexflow_tpu.obs.trace import span

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
RECORDED = os.path.join(ROOT, "benchmarks", "tests",
                        "recorded_turn.xplane.pb")
CFG = {"n_routed_experts": 16, "moe_topk": 6, "num_layers": 2}

#: a decode dispatch of 4 slots, 3 rows past their prompt
STEP = dict(rows=3, feeding=0, slots=4, kv_blocks_live=6, kv_blocks_dense=32,
            kv_blocks_read=32, moe_pairs=12, moe_dropped=0, moe_max_rows=4,
            moe_hit=8, moe_zero_picks=16, moe_real_min=2, moe_real_max=6,
            loop_steps=4, exit_mass_0=0.25, exit_mass_1=0.75,
            eva_rows_window=30, eva_rows_summary=10, eva_rows_read=400)
#: a sampling pass: 4 live rows, 2 of them sampled, 10 real tokens
PASS = dict(rows=4, tokens=10, passes=1, capacity=16, decode_rows=2, slots=4,
            kv_blocks_live=10, kv_blocks_dense=32, kv_blocks_read=32,
            moe_pairs=36, moe_dropped=0, moe_max_rows=9, moe_hit=12,
            moe_zero_picks=30, moe_real_min=1, moe_real_max=6,
            loop_steps=4, exit_mass_0=0.5, exit_mass_1=0.5,
            eva_rows_window=90, eva_rows_summary=30, eva_rows_read=400)
#: a pass of the scan (or the parent's): nothing sampled from it
SCAN = dict(rows=1, tokens=8, passes=8, capacity=32, kv_blocks_live=4,
            kv_blocks_dense=256, kv_blocks_read=256)

MOVED = {"step": dict(program="step", arg_leaves=80, host_bytes=288),
         "prefill": dict(program="prefill", arg_leaves=81, host_bytes=416)}
BYTES = {"step": 1024, "prefill": 1048}


def iteration(kind, args, named=True):
    """One scheduler turn as the program opens its spans: the dispatch
    with its enqueue and fetch, then sampling and observing."""
    program = "step" if kind == "decode" else "prefill"
    with span("sched.iteration"):
        with span("sched.admit"):
            pass
        with span(f"sched.{kind}.prepare"):
            pass
        with span(f"sched.{kind}.dispatch", **args):
            with span("model.enqueue", first=0,
                      **(MOVED[program] if named else {})):
                pass
            if "decode_rows" in args or kind == "decode":
                with span("model.fetch", **(dict(
                        program=program, bytes=BYTES[program])
                        if named else {})):
                    pass
        with span("sched.sample"):
            time.sleep(0.0005)
        with span("sched.observe"):
            pass


def stretch(turns, named=True):
    """A context whose traced stretch holds the `turns` ([(kind, args)]),
    and what the readers said."""
    t0 = time.monotonic()
    for kind, args in turns:
        iteration(kind, args, named)
    said = []
    return types.SimpleNamespace(
        _trace_t0=t0, trace_window_s=time.monotonic() - t0, cfg=CFG,
        trace_summary=None, trace_dir=None, out=said.append), said


RINGS = {
    "decode_only": [("decode", STEP)] * 3,
    "passes_only": [("prefill", PASS)] * 2,
    "both": [("prefill", PASS), ("decode", STEP), ("prefill", PASS),
             ("prefill", SCAN), ("decode", STEP)],
}
# metric -> {ring: value}; the ring's arithmetic by hand
WANT = {
    "sampled.rows": {"decode_only": 3.0, "passes_only": 2.0,
                     "both": (2 + 3 + 2 + 3) / 4},
    "sampled.kv_read_share": {"decode_only": 100 * 6 / 32,
                              "passes_only": 100 * 10 / 32,
                              "both": 100 * 32 / 128},
    "sampled.moe_held_pairs": {"decode_only": 12.0, "passes_only": 36.0,
                               "both": 24.0},
    "sampled.moe_load_max_over_mean": {"decode_only": 4 * 16 / 12,
                                       "passes_only": 9 * 16 / 36,
                                       "both": 26 * 16 / 96},
    # a decode dispatch picks for every slot, a pass for its real tokens
    "sampled.moe_zero_pick_share": {
        "decode_only": 100 * 16 / (4 * 6 * 2),
        "passes_only": 100 * 30 / (10 * 6 * 2),
        "both": 100 * 92 / ((4 + 10 + 4 + 10) * 6 * 2)},
    "sampled.loop_weight_passes": {"decode_only": 4.0, "passes_only": 4.0,
                                   "both": 4.0},
    "sampled.exit_expected_pass": {"decode_only": 1.75, "passes_only": 1.5,
                                   "both": 1.625},
    "sampled.eva_live_over_read": {"decode_only": 10.0, "passes_only": 30.0,
                                   "both": 20.0},
}
TURN = ("turn.enqueue_ms", "turn.between_ms")
DEVICE = ("turn.launch_lag_ms", "turn.fetch_tail_ms")


def read(name, ctx):
    metric = {"name": f"{name}.capacity"}
    return load_module("readers", metric["name"]).read(ctx, metric)


@pytest.mark.parametrize("ring", sorted(RINGS))
@pytest.mark.parametrize("name", sorted(WANT))
def test_sampled_reader_on_a_recorded_ring(name, ring):
    ctx, said = stretch(RINGS[ring])
    assert read(name, ctx) == pytest.approx(WANT[name][ring])
    assert len(said) <= 1


@pytest.mark.parametrize("ring", sorted(RINGS))
@pytest.mark.parametrize("name", TURN)
def test_turn_reader_on_a_recorded_ring(name, ring):
    ctx, said = stretch(RINGS[ring])
    got = read(name, ctx)
    fetched = sum("decode_rows" in a or k == "decode" for k, a in RINGS[ring])
    if name == "turn.enqueue_ms":
        assert got > 0  # (an empty span: microseconds on an idle host)
        programs = {"step" if k == "decode" else "prefill"
                    for k, _ in RINGS[ring]}
        assert len(said) == len(programs)
        for program in programs:
            (line,) = [s for s in said if f" {program}: " in s]
            assert f"{MOVED[program]['arg_leaves']} argument leaves" in line
            assert f"{MOVED[program]['host_bytes']} host bytes" in line
    else:
        # the turn between two dispatches holds one `sched.sample`,
        # which sleeps half a millisecond (longer on a busy host)
        assert got > 0.5
        (line,) = said  # no device numbers here: no closure line
        assert f"over {fetched - 1}," in line
        assert "sched.sample=" in line and "sched.admit=" in line
        assert "model.fetch" not in line and "model.enqueue" not in line


@pytest.mark.parametrize("name", sorted(WANT) + list(TURN) + list(DEVICE))
def test_reader_without_a_traced_stretch(name):
    ctx = types.SimpleNamespace(_trace_t0=None, trace_window_s=None, cfg=CFG,
                                trace_summary=None, trace_dir=None,
                                out=lambda s: pytest.fail(s))
    assert read(name, ctx) is None


@pytest.mark.parametrize("name", list(TURN) + list(DEVICE))
def test_turn_readers_leave_a_tree_without_program_args_out(name):
    """The parent of PR 53: the same spans, none says its program."""
    ctx, said = stretch(RINGS["both"], named=False)
    assert read(name, ctx) is None and not said


def test_a_stretch_of_scans_alone_samples_nothing():
    ctx, said = stretch([("prefill", SCAN)] * 2)
    for name in WANT:
        assert read(name, ctx) is None
    assert not said


def test_betweens_split_the_turn_by_innermost_span():
    from flexflow_tpu.obs.trace import spans

    t0 = time.monotonic()
    stretch(RINGS["both"])
    mine = [r for r in spans() if r.t_start >= t0]
    turns = sd.betweens(mine)
    # a fetch before every enqueue but the first; the scan's pass is
    # not fetched, so the turn before it ends at its enqueue and the
    # decode dispatch behind it starts none
    assert len(turns) == 3
    for seconds, split in turns:
        assert seconds == pytest.approx(sum(split.values()), rel=1e-6)
        assert split["sched.sample"] > 0.4e-3
        assert not {"model.fetch", "model.enqueue"} & set(split)
    calls = sd.model_calls(mine)
    assert [r.args["program"] for r in calls] == [
        "prefill", "prefill", "step", "step", "prefill", "prefill",
        "prefill", "step", "step"]


# -- the xplane half, on hand-made planes ------------------------------------------
def host(name, start, end, **stats):
    return hs.HostSpan(name, start, end, stats)


def planes(skew):
    """Three iterations on one thread: a sampling pass, the scan's pass
    with a decode step queued behind it, a decode step; the device
    plane ``skew`` seconds early."""
    spans, modules = [], {"jit_prefill": [], "jit_step": []}
    t = 1.0

    def call(program, fetched, lag, busy, tail, queued_behind=0.0):
        nonlocal t
        spans.append(host("model.enqueue", t, t + 0.001, program=program,
                          first=0))
        start = max(t + lag, queued_behind)
        modules[sd.PROGRAMS[program]].append(
            (start - skew, start + busy - skew, busy))
        t += 0.001
        if fetched:
            spans.append(host("model.fetch", t, start + busy + tail,
                              program=program, bytes=64))
            t = start + busy + tail + 0.002  # the turn between
        return start + busy

    call("prefill", True, 0.0004, 0.020, 0.0015)
    end = call("prefill", False, 0.0004, 0.030, 0.0)
    call("step", True, 0.0004, 0.010, 0.0015, queued_behind=end)
    call("step", True, 0.0006, 0.010, 0.0025)
    return spans, modules


@pytest.mark.parametrize("skew_ms", [-0.8, 0.0, 0.3, 1.2])
def test_two_sided_shift_restores_causality_on_hand_made_planes(skew_ms):
    spans, modules = planes(1e-3 * skew_ms)
    turns = sd.pair_turns(spans, modules)
    assert [(t.program, t.fetch is not None, t.idle_launch)
            for t in turns] == [("prefill", True, True),
                                ("prefill", False, True),
                                ("step", True, False), ("step", True, True)]
    shift, lo, hi = sd.two_sided_shift_s(turns)
    # feasible: from the least lag below the truth to the least tail
    # above it, and the shift is the point of it nearest to no shift
    assert lo == pytest.approx(1e-3 * skew_ms - 0.0004)
    assert hi == pytest.approx(1e-3 * skew_ms + 0.0015)
    assert lo <= shift <= hi
    assert shift == pytest.approx(min(max(0.0, lo), hi))
    moved = [t._replace(run=(t.run[0] + shift, t.run[1] + shift, t.run[2]))
             for t in turns]
    for t in moved:
        assert t.run[0] >= t.enqueue.start_s - 1e-9
        if t.fetch is not None:
            assert t.fetch.start_s <= t.run[1] <= t.fetch.end_s + 1e-9
    # lag + tail of a dispatch does not depend on the shift
    for t, was in zip(moved, turns):
        if t.fetch is not None:
            assert (t.run[0] - t.enqueue.start_s) + (t.fetch.end_s - t.run[1]) \
                == pytest.approx((was.run[0] - was.enqueue.start_s)
                                 + (was.fetch.end_s - was.run[1]))


def test_runs_the_stretch_cut_from_their_enqueue_are_left_out():
    spans, modules = planes(0.0)
    # a run that was launched before the session opened
    modules["jit_prefill"].insert(0, (0.90, 0.92, 0.02))
    turns = sd.pair_turns(spans, modules)
    assert len(turns) == 4 and turns[0].run[0] > 0.99
    # and an enqueue whose run the session's end cut off
    spans.append(host("model.enqueue", 9.0, 9.001, program="step", first=0))
    assert len(sd.pair_turns(spans, modules)) == 4
    # spans that do not say their program: nothing to pair
    bare = [s._replace(stats={}) for s in spans]
    assert sd.pair_turns(bare, modules) == []


# -- the xplane half, on the recording ------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    if not os.path.isfile(RECORDED):
        pytest.fail(f"{RECORDED} is missing: record it on the chip with "
                    "benchmarks/tools/record_turn_spans.py")
    assert os.path.getsize(RECORDED) < 1 << 20
    summary = rt.reduce(RECORDED, 1)
    said = []
    ctx = types.SimpleNamespace(
        trace_summary=summary, trace_dir=os.path.dirname(RECORDED),
        _trace_t0=None, trace_window_s=None, cfg=CFG, out=said.append)
    # (`host_spans.xplane_of` looks for the newest *.xplane.pb under the
    # directory: hand the view over as `device_view` would leave it)
    line = hs.dispatch_line(hs.read_host(RECORDED))
    planes = [p for p in rt.read_planes(RECORDED) if p["ops"]]
    ctx.host_device_view = (line, planes[0]["ops"], summary["modules"], 0.0)
    return ctx, said, line, summary


def test_recorded_spans_say_what_they_moved(recorded):
    _, _, line, summary = recorded
    enqueues = [s for s in line if s.name == "model.enqueue"]
    fetches = [s for s in line if s.name == "model.fetch"]
    assert {s.stats["program"] for s in enqueues} == {"step", "prefill"}
    assert all(s.stats["arg_leaves"] > 3 and s.stats["host_bytes"] > 0
               for s in enqueues)
    assert all(s.stats["bytes"] > 0 for s in fetches)
    passes = [s for s in line if s.name == "sched.prefill.dispatch"]
    assert passes and all("decode_rows" in s.stats and "moe_pairs" in s.stats
                          and s.stats["moe_dropped"] == 0 for s in passes)
    assert {"jit_step", "jit_prefill"} <= set(summary["modules"])


def test_recorded_runs_pair_with_their_enqueue_and_end_inside_their_fetch(
        recorded):
    ctx, said, line, summary = recorded
    turns = sd.turn_view(ctx)
    runs = sum(len(summary["modules"][m]) for m in ("jit_step", "jit_prefill"))
    assert len(turns) == runs >= 6
    assert {t.program for t in turns} == {"step", "prefill"}
    # the toy pass samples: every run is fetched, onto an idle device
    assert all(t.fetch is not None and t.idle_launch for t in turns)
    # the planes disagreed by 0.39 ms in this session: a run "started"
    # that long before its enqueue, and the shift is the least that
    # mends it, well inside what the fetches allow
    (clock,) = [s for s in said if s.startswith("turn clock:")]
    assert "shifted by +0.3" in clock and "fetched runs end inside" in clock
    for t in turns:
        assert t.run[0] >= t.enqueue.start_s - 1e-9
        # (a toy program runs 20-100 us: it is over before the host
        # gets to `model.fetch`; a cell's ends inside it)
        assert t.enqueue.end_s - 1e-3 < t.run[1] <= t.fetch.end_s
    # read once a run
    assert sd.turn_view(ctx) is turns


def test_lag_and_tail_of_the_recording(recorded):
    ctx, said, *_ = recorded
    lag, tail = read("turn.launch_lag_ms", ctx), read("turn.fetch_tail_ms", ctx)
    # a toy program runs a fraction of a millisecond: the jitted call
    # and the logits' way back are the iteration
    assert 0 <= lag < 5 and 0 <= tail < 5
    assert any(s.startswith("turn.launch_lag_ms: prefill:") for s in said)
    assert any(s.startswith("turn.fetch_tail_ms: step:") and "bytes a fetch"
               in s for s in said)
    # the device's idle time a dispatch is the turn: lag + tail and what
    # the host does between a fetch and the next enqueue
    idle = load_module("readers", "sched.dispatch_ms").read(ctx, {})
    assert lag + tail < idle
