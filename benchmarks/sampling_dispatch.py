"""The serving step, whichever program ran it (ISSUE 53).

A *sampling dispatch* is a dispatch whose logits are fetched and
sampled: a `sched.decode.dispatch` span, or a `sched.prefill.dispatch`
span that carries `decode_rows` (the one-pass prefill program, which
returns each row's logits at its last real token; above a knee it is
the whole serving iteration and the stretch holds no `jit_step` at
all).  The readers of `decode_dispatch.py` and `readers/decode.*.py`
look at the decode program alone; what is here looks at either.

From the span ring (program_counter / program_span):

* `dispatches(ctx)`: the stretch's sampling dispatches, in order;
* `dispatch_args(ctx, *names)`: per name the sum and the mean over the
  sampling dispatches that carry every one of them (None where none
  does: a family without such layers, or the parent of the PR that
  brought the arg), as `decode_dispatch.dispatch_args` for one program;
* `model_calls(records)`: the dispatching thread's `model.enqueue` and
  `model.fetch` records of the step programs (`program` in `PROGRAMS`;
  None on a tree whose spans do not say their program);
* `betweens(records)`: per fetched dispatch, the host's turn from its
  `model.fetch`'s end to the next `model.enqueue`'s start on that
  thread, cut by the innermost span open (`host_spans.innermost_segments`).

From the traced stretch's xplane (device_trace), `turn_view(ctx)`: the
host and device planes on one clock with BOTH causal bounds.
`host_spans.device_view` parses the xplane once a run and shifts the
device plane by a bound from below (no `jit_prefill` starts before its
host dispatch span) and, from above, by the `jit_step` programs alone
(none ends after the fetch that waited for it).  Here its shift is
undone and the device plane shifted again under both bounds from EVERY
step program: a run is paired with ITS `model.enqueue` and ITS
`model.fetch` by the spans' `program` and their order on the host
plane (not by nearest start, which mispairs a program that queues
behind another), no run may start before its enqueue span does, and no
fetched run may end after its fetch does.  The shift nearest to 0 in
that interval is taken and said on an earlier line with the interval:
`lag + tail` of a dispatch is measured to a fraction of a millisecond,
their split to the interval's width (about a millisecond, as
`host_spans.py` says).
"""
from __future__ import annotations

from collections import defaultdict, namedtuple

from benchmarks import host_spans as hs

#: `model.enqueue` / `model.fetch` `program` -> the jitted program's
#: name on the device plane (`reduce_trace.module_name`)
PROGRAMS = {"step": "jit_step", "prefill": "jit_prefill",
            "verify": "jit_verify"}
#: further than any skew seen between the planes (1.2 ms, PR 26)
SKEW_S = 2e-3

#: one device run with the host spans that launched and awaited it
#: (`fetch` None: a program nobody waits for, the scan's prefill);
#: `idle_launch`: nothing was queued before it (the call before its
#: enqueue on the host plane was a fetch), so the device WAITED for it
Turn = namedtuple("Turn", "program run enqueue fetch idle_launch")


# -- the ring ----------------------------------------------------------------
def dispatches(ctx):
    """The stretch's sampling dispatches (ring records, oldest first),
    or None without a ring or a traced stretch."""
    found = hs.ring(ctx)
    if found is None:
        return None
    return [r for r in found[0]
            if r.name == "sched.decode.dispatch"
            or (r.name == "sched.prefill.dispatch"
                and "decode_rows" in r.args)]


def dispatch_args(ctx, *names):
    """{name: (sum, mean)} over the stretch's sampling dispatches that
    carry all of ``names``, with ``"n"``: how many; None where no
    sampling dispatch carries them."""
    found = dispatches(ctx)
    spans = [r for r in found or () if all(n in r.args for n in names)]
    if not spans:
        return None
    out = {n: (sum(r.args[n] for r in spans),
               sum(r.args[n] for r in spans) / len(spans)) for n in names}
    out["n"] = len(spans)
    return out


def by_program(spans):
    """'5 decode dispatches and 120 passes'."""
    steps = sum(r.name == "sched.decode.dispatch" for r in spans)
    return f"{steps} decode dispatches and {len(spans) - steps} passes"


def dispatching_thread(records):
    """The records of the thread with the most ``*.dispatch`` spans."""
    n = defaultdict(int)
    for r in records:
        n[r.thread] += r.name.endswith(".dispatch")
    if not n or not max(n.values()):
        return []
    thread = max(n, key=n.get)
    return [r for r in records if r.thread == thread]


def model_calls(records):
    """The dispatching thread's `model.enqueue` (lazy compiles left out:
    `first` 0) and `model.fetch` records of the step programs, by start;
    None where no such record names its `program`."""
    calls = [r for r in dispatching_thread(records)
             if r.name in ("model.enqueue", "model.fetch")
             and r.args.get("program") in PROGRAMS
             and not r.args.get("first")]
    return sorted(calls, key=lambda r: r.t_start) or None


def betweens(records):
    """[(seconds, {innermost span: seconds})] per `model.fetch` of the
    dispatching thread that is followed by a `model.enqueue`: the
    host's turn between two dispatches; None as `model_calls`."""
    calls = model_calls(records)
    if calls is None:
        return None
    mine = sorted(dispatching_thread(records),
                  key=lambda r: (r.t_start, -r.t_end))
    segments = hs.innermost_segments(
        [hs.HostSpan(r.name, r.t_start, r.t_end, r.args) for r in mine])
    out, j = [], 0
    for a, b in zip(calls, calls[1:]):
        if a.name != "model.fetch" or b.name != "model.enqueue":
            continue
        lo, hi, split = a.t_end, b.t_start, defaultdict(float)
        while j < len(segments) and segments[j][1] <= lo:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < hi:
            s, e, name = segments[k]
            if min(e, hi) > max(s, lo):
                split[name] += min(e, hi) - max(s, lo)
            k += 1
        if hi - lo > sum(split.values()):  # no span open: the loop's own
            split[hs.OUTSIDE] = hi - lo - sum(split.values())
        out.append((hi - lo, dict(split)))
    return out


# -- the xplane ---------------------------------------------------------------
def pair_turns(spans, modules):
    """[Turn] of the stretch: per step program, its `model.enqueue`
    spans (``spans``: the dispatching thread's `host_spans.HostSpan`s,
    whose stats carry `program`) paired in order with its runs on the
    device plane (``modules``: {program name: [(start_s, end_s,
    busy_s)]}, runs that start before the first recorded enqueue left
    out: the stretch's opening cut their span off), each with the
    `model.fetch` of the same program that follows the enqueue before
    the next one does.  [] where the spans do not say their program."""
    calls = sorted((s for s in spans
                    if s.name in ("model.enqueue", "model.fetch")
                    and s.stats.get("program") in PROGRAMS
                    and not s.stats.get("first")),
                   key=lambda s: s.start_s)
    turns = []
    for program, module in PROGRAMS.items():
        mine = [(i, s) for i, s in enumerate(calls)
                if s.stats["program"] == program]
        enqueues = [(i, s) for i, s in mine if s.name == "model.enqueue"]
        if not enqueues:
            continue
        runs = [r for r in sorted(modules.get(module, []))
                if r[0] >= enqueues[0][1].start_s - SKEW_S]
        for (i, enq), run in zip(enqueues, runs):
            nxt = calls[i + 1] if i + 1 < len(calls) else None
            fetch = (nxt if nxt is not None and nxt.name == "model.fetch"
                     and nxt.stats["program"] == program else None)
            idle = i == 0 or calls[i - 1].name == "model.fetch"
            turns.append(Turn(program, run, enq, fetch, idle))
    return sorted(turns, key=lambda t: t.run[0])


def two_sided_shift_s(turns):
    """(shift, lo, hi): seconds to ADD to the device plane so that no
    run starts before its enqueue span (shift >= lo) and no fetched run
    ends after its fetch span (shift <= hi): the feasible shift nearest
    to 0; the middle of [hi, lo] where the two cross (clock noise)."""
    lags = [t.run[0] - t.enqueue.start_s for t in turns]
    tails = [t.fetch.end_s - t.run[1] for t in turns if t.fetch is not None]
    lo = -min(lags) if lags else float("-inf")
    hi = min(tails) if tails else float("inf")
    if lo > hi:
        return (lo + hi) / 2, lo, hi
    return min(max(0.0, lo), hi), lo, hi


def turn_view(ctx):
    """[Turn] with the device plane on the host's clock under both
    bounds (module docstring), the shift and the count of runs that end
    inside their fetch said on an earlier line; None without a trace or
    on a tree whose spans do not say their program.  Read once a run."""
    if getattr(ctx, "turn_view", None) is not None:
        return ctx.turn_view or None
    ctx.turn_view = []
    view = hs.device_view(ctx)
    if view is None:
        return None
    spans, _, modules, was = view
    turns = pair_turns(spans, {
        k: [(a - was, b - was, busy) for a, b, busy in v]
        for k, v in modules.items()})
    if not turns:
        return None
    shift, lo, hi = two_sided_shift_s(turns)
    turns = [t._replace(run=(t.run[0] + shift, t.run[1] + shift, t.run[2]))
             for t in turns]
    fetched = [t for t in turns if t.fetch is not None]
    inside = sum(t.fetch.start_s <= t.run[1] <= t.fetch.end_s
                 for t in fetched)
    ctx.out(f"turn clock: device plane shifted by {1e3 * shift:+.3f} ms "
            f"(host_spans.device_view took {1e3 * was:+.3f}); no run starts "
            f"before its model.enqueue from {1e3 * lo:+.3f}, none ends after "
            f"its model.fetch up to {1e3 * hi:+.3f}; {inside} of "
            f"{len(fetched)} fetched runs end inside their fetch "
            + " ".join(f"{p}={sum(t.program == p for t in fetched)}"
                       for p in PROGRAMS
                       if any(t.program == p for t in turns)))
    ctx.turn_view = turns
    return turns


def mean_ms(seconds):
    seconds = list(seconds)
    return 1e3 * sum(seconds) / len(seconds) if seconds else None
