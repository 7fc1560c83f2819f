"""The program's host spans, from both of their sinks, and the device's
idle time laid at them.

`flexflow_tpu.obs.trace.span` (ISSUE 26) records every span twice: as a
`jax.profiler.TraceAnnotation`, which lands on the xplane's ``/host:CPU``
plane whenever a profiler session is open, on the line of the thread
that made it and on the clock of the device planes (`reduce_trace.py`
reads those); and as a record in a process-wide ring, on
`time.monotonic()`, the clock of `ctx._trace_t0`.

From the traced stretch's ``*.xplane.pb``:

* `read_host(path)`: ``{line: [HostSpan(name, start_s, end_s, stats)]}``,
  the program's spans (told from the Python tracer's and jax's own
  events by `PROGRAM_SPAN`) per thread line, sorted by start;
* `dispatch_line(host)`: the line of the thread that dispatches (the one
  with the most ``*.dispatch`` spans);
* `idle_by_host_span(spans, device_ops)`: the device's idle seconds (the
  gaps between its merged operations, so their sum is the window less
  the busy union) split by the INNERMOST program span open on that
  thread at that instant, or ``outside_program``;
* `fetch_tails(spans, module_runs)`: per ``model.fetch`` span, its end
  less the end of the device program that ends inside it.

The two planes' clocks are only mapped onto each other when a session
opens, and by a millisecond or so differently each time (PR 26: device
programs "start" up to 1.2 ms before the host span that enqueued them in
one session, 0.9 ms after it in the next).  `causal_shift_s` gives the
least shift of the device plane that restores causality (no program
starts before its host dispatch span does, none ends after the fetch
that waited for it); `device_view(ctx)` applies it once for all readers
and says so on an earlier line.  What such a trace can say is therefore
good to about a millisecond: the SUM of a dispatch's enqueue lag and
fetch tail is measured, their split is bounded.

From the ring, `ring(ctx)`: the records that lie inside the stretch
``[ctx._trace_t0, ctx._trace_t0 + ctx.trace_window_s]`` and those that
lie outside it (the same spans with the profiler closed: the difference
is what the open profiler costs the host).  A program without the ring
(the parent of the PR that brought it) gives ``None``, and every reader
then leaves its metric out.

Readers (`readers/*.py`) print their table through ``ctx.out`` on an
earlier line and return one number.
`benchmarks/tests/test_host_spans.py` checks the xplane half on a small trace recorded on a TPU v5e.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict, namedtuple

from benchmarks.reduce_trace import merged

HOST_PLANE = "/host:CPU"
# the program's span vocabulary (docs/OBSERVABILITY.md "Host spans")
PROGRAM_SPAN = re.compile(
    r"^(sched|model|serve|fit|train_step|compile)\.[a-z_.]+$"
    r"|^(train_step|compile|search|init_weights|build_step_fns"
    r"|host_transfer|device_drain)$")
OUTSIDE = "outside_program"

HostSpan = namedtuple("HostSpan", "name start_s end_s stats")


# -- the xplane's host plane ----------------------------------------------
def read_host(path: str) -> dict:
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != HOST_PLANE:
            continue
        for n, line in enumerate(plane.lines):
            found = [HostSpan(ev.name, ev.start_ns * 1e-9,
                              (ev.start_ns + ev.duration_ns) * 1e-9,
                              dict(ev.stats))
                     for ev in line.events if PROGRAM_SPAN.match(ev.name)]
            if found:
                # (every Python thread's line is called "python")
                out[f"{line.name}#{n}"] = sorted(
                    found, key=lambda s: (s.start_s, -s.end_s))
    return out


def dispatch_line(host: dict):
    """The spans of the thread that dispatches; [] where no line has a
    ``*.dispatch`` span."""
    def dispatches(spans):
        return sum(s.name.endswith(".dispatch") for s in spans)

    best = max(host.values(), key=dispatches, default=[])
    return best if dispatches(best) else []


def innermost_segments(spans):
    """[(start_s, end_s, name)]: the thread's timeline cut into the
    stretches during which one span was the innermost open one.  Spans
    of one thread nest; they come sorted by (start, -end)."""
    out, stack = [], []   # stack of [name, end, cursor]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, cursor = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for s in spans:
        close(s.start_s)
        if stack and s.start_s > stack[-1][2]:
            out.append((stack[-1][2], s.start_s, stack[-1][0]))
        stack.append([s.name, s.end_s, s.start_s])
    close(float("inf"))
    return sorted(out)


def idle_by_host_span(spans, device_ops) -> dict:
    """{span name or OUTSIDE: idle seconds}; ``device_ops`` are
    [(name, start_s, end_s)] of one chip (`reduce_trace.read_planes`)."""
    busy = merged([(s, e) for _, s, e in device_ops])
    gaps = [(e0, s1) for (_, e0), (s1, _) in zip(busy, busy[1:]) if s1 > e0]
    segments = innermost_segments(spans)
    out, j = defaultdict(float), 0
    for a, b in gaps:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k, pos = j, a
        while k < len(segments) and segments[k][0] < b:
            s, e, name = segments[k]
            if s > pos:
                out[OUTSIDE] += s - pos
            lo, hi = max(s, pos), min(e, b)
            if hi > lo:
                out[name] += hi - lo
            pos = max(pos, hi)
            k += 1
        if b > pos:
            out[OUTSIDE] += b - pos
    return dict(out)


def fetch_tails(spans, module_runs):
    """[(tail_s, program_end_inside_a_fetch)] for the device program's
    dispatches ``module_runs`` [(start_s, end_s, busy_s)]: the end of
    the ``model.fetch`` span the host was waiting in less the program's
    end (the logits' way to the host).  A dispatch whose end lies in no
    fetch span (cut by the stretch's edge) gives (None, False)."""
    fetches = [s for s in spans if s.name == "model.fetch"]
    out, j = [], 0
    for _, end, _ in sorted(module_runs):
        while j < len(fetches) and fetches[j].end_s < end:
            j += 1
        if j < len(fetches) and fetches[j].start_s <= end:
            out.append((fetches[j].end_s - end, True))
        else:
            out.append((None, False))
    return out


def starts_in_order(spans, name, module_runs, slack_s=0.0):
    """(matched, late): the device program's dispatches paired in order
    with the host spans called ``name``, and in how many pairs the
    device did NOT start between its own host span's start and the next
    one's (0 on a shared clock: host and device starts interleave).
    Dispatches that start before the first such host span does belong
    to spans the stretch's opening cut off, and are left out; so a
    device clock that runs EARLY by less than a dispatch's length hides
    in that alignment, and what bounds the skew is causality: a program
    cannot start before its enqueue (`in_order`'s lags) nor end after
    the fetch that waited for it (`fetch_tails`).  ``slack_s`` allows for the planes' clocks disagreeing by that much
    (the device's is mapped onto the host's when the session opens; the
    recorded toy trace shows up to 0.5 ms)."""
    pairs = in_order(spans, name, module_runs, slack_s)
    nxt = [h.start_s for h, _ in pairs[1:]] + [float("inf")]
    return len(pairs), sum(
        not h.start_s - slack_s <= run[0] < following + slack_s
        for (h, run), following in zip(pairs, nxt))


def in_order(spans, name, module_runs, slack_s=0.0):
    """[(host span, device dispatch)] paired in order, from the first
    dispatch that starts no earlier than the first host span does."""
    hosts = [s for s in spans if s.name == name]
    if not hosts:
        return []
    runs = [r for r in sorted(module_runs)
            if r[0] >= hosts[0].start_s - slack_s]
    return list(zip(hosts, runs))


def nearest_lags(spans, name, module_runs):
    """[device start - host start] per host span called ``name``, against
    the device dispatch that starts nearest to it; pairs further apart
    than half the device's dispatch spacing (a dispatch the stretch cut
    from its span) are left out."""
    starts = sorted(r[0] for r in module_runs)
    if len(starts) < 2:
        return []
    spacing = sorted(b - a for a, b in zip(starts, starts[1:]))
    limit = spacing[len(spacing) // 2] / 2
    lags = []
    for h in (s for s in spans if s.name == name):
        i = bisect.bisect_left(starts, h.start_s)
        near = min(starts[max(0, i - 1):i + 1],
                   key=lambda t: abs(t - h.start_s))
        if abs(near - h.start_s) < limit:
            lags.append(near - h.start_s)
    return lags


def causal_shift_s(spans, modules, dispatches, fetched) -> float:
    """Seconds to ADD to the device plane's times so that no program
    starts before its host dispatch span (``dispatches``: {span name:
    program name}) and no ``fetched`` program ends after the
    `model.fetch` span that waited for it: the feasible shift nearest to
    0 (0 where the planes already agree)."""
    lags = [lag for name, program in dispatches.items()
            for lag in nearest_lags(spans, name, modules.get(program, []))]
    ends = sorted(s.end_s for s in spans if s.name == "model.fetch")
    tails = []
    for _, end, _ in modules.get(fetched, []):
        i = bisect.bisect_left(ends, end - 0.02)   # skew is far under 20 ms
        if i < len(ends) and ends[i] - end < 0.05:
            tails.append(ends[i] - end)
    lo = -min(lags) if lags else 0.0      # shift >= lo
    hi = min(tails) if tails else 0.0     # shift <= hi
    if lo > 0:
        return lo
    return hi if hi < 0 else 0.0


# host dispatch spans whose program starts as soon as it is enqueued (a
# decode program queues behind the prefill program before it)
DISPATCHES = {"sched.prefill.dispatch": "jit_prefill"}


def device_view(ctx):
    """(dispatching thread's spans, first chip's ops, its programs'
    dispatches by name, shift): the stretch's host and device planes on
    one clock, the device's shifted by `causal_shift_s`; None without a
    trace.  Read once a run."""
    if getattr(ctx, "host_device_view", None) is not None:
        return ctx.host_device_view
    xplane = xplane_of(ctx)
    if not ctx.trace_summary or xplane is None:
        return None
    from benchmarks import reduce_trace

    planes = [p for p in reduce_trace.read_planes(xplane) if p["ops"]]
    spans = dispatch_line(read_host(xplane))
    if not planes or not spans:
        return None
    modules = ctx.trace_summary["modules"]
    shift = causal_shift_s(spans, modules, DISPATCHES, "jit_step")
    if shift:
        ctx.out(f"clock: device plane shifted by {1e3 * shift:+.3f} ms, the "
                "least that lets no program start before its host span nor "
                "end after the fetch that waited for it")
    ctx.host_device_view = (
        spans, [(n, a + shift, b + shift) for n, a, b in planes[0]["ops"]],
        {k: [(a + shift, b + shift, busy) for a, b, busy in v]
         for k, v in modules.items()}, shift)
    return ctx.host_device_view


# -- the ring --------------------------------------------------------------
def ring(ctx):
    """(inside, outside, all) lists of the ring's records by the traced
    stretch, or None where the program has no ring or no stretch ran."""
    try:
        from flexflow_tpu.obs.trace import spans
    except ImportError:
        return None
    t0 = getattr(ctx, "_trace_t0", None)
    if t0 is None or ctx.trace_window_s is None:
        return None
    t1 = t0 + ctx.trace_window_s
    records = spans()
    inside = [r for r in records if r.t_start >= t0 and r.t_end <= t1]
    outside = [r for r in records if r.t_end <= t0 or r.t_start >= t1]
    return inside, outside, records


def named(records, name):
    return [r for r in records if r.name == name]


def dur(record) -> float:
    return record.t_end - record.t_start


def mean_ms(records):
    return 1e3 * sum(map(dur, records)) / len(records) if records else None


def children(records):
    """{parent span_id: [records]}."""
    out = defaultdict(list)
    for r in records:
        if r.parent_id is not None:
            out[r.parent_id].append(r)
    return out


def compile_child_seconds(ctx, name):
    """Seconds in the children called ``name`` of the top-level
    `FFModel.compile` (the first `compile` span with no parent: a decode
    twin's sits under `serve.build_twin`); all children on an earlier
    line."""
    found = ring(ctx)
    if found is None:
        return None
    top = next((r for r in named(found[2], "compile")
                if r.parent_id is None), None)
    if top is None:
        return None
    kids = children(found[2]).get(top.span_id, [])
    if not getattr(ctx, "compile_table_printed", False):
        ctx.compile_table_printed = True  # two readers share the table
        ctx.out(f"compile ({top.args.get('ops')} ops) {dur(top):.3f} s: "
                + " ".join(f"{k.name}={dur(k):.3f}" for k in kids))
    picked = [k for k in kids if k.name == name]
    return sum(map(dur, picked)) if picked else None


def xplane_of(ctx):
    """The traced stretch's ``*.xplane.pb``, or None."""
    from benchmarks.run import find_xplane

    return find_xplane(ctx.trace_dir) if ctx.trace_dir else None


def fmt(value, unit="ms"):
    return "none" if value is None else f"{value:.4f} {unit}"
