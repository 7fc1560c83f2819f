"""`sched.self_ms.capacity`: the scheduler loop's own host time per
dispatch, ms: over the loop turns (`sched.iteration` spans) that lie in
the traced stretch and dispatched something, their durations less their
``*.dispatch`` children, over the number of those dispatches.  Host work
that runs while the asynchronous prefill program keeps the device busy
counts here and not in `sched.dispatch_ms.capacity` (the device's idle
time per dispatch).  The earlier line gives the same figure for the
turns OUTSIDE the stretch, where the profiler was closed: the
difference is the open profiler's cost to the host (program_span)."""
from benchmarks import host_spans as hs


def self_ms_per_dispatch(records):
    kids = hs.children(records)
    total = n = 0
    for it in hs.named(records, "sched.iteration"):
        dispatches = [k for k in kids.get(it.span_id, ())
                      if k.name.endswith(".dispatch")]
        if dispatches:
            total += hs.dur(it) - sum(map(hs.dur, dispatches))
            n += len(dispatches)
    return (1e3 * total / n if n else None), n


def read(ctx, metric):
    found = hs.ring(ctx)
    if found is None:
        return None
    inside, outside, _ = found
    value, n = self_ms_per_dispatch(inside)
    out_value, out_n = self_ms_per_dispatch(outside)
    ctx.out(f"sched.self_ms: in the stretch {hs.fmt(value)} a dispatch "
            f"over {n} dispatches; outside it (profiler closed) "
            f"{hs.fmt(out_value)} over {out_n}")
    return value
