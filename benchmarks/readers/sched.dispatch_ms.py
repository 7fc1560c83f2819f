"""`sched.dispatch_ms.latency` / `.capacity`: what the scheduler loop
adds to a dispatch, ms: on the device's timeline, the time from one
step program's start to the next one's start less the time an
operation ran in between, averaged over the traced dispatches of the
decode and prefill programs.  The benchmark's own reduction; the
scheduler's in-program spans are the tracing issue's (device_trace)."""
PROGRAMS = ("jit_step", "jit_prefill")


def read(ctx, metric):
    t = ctx.trace_summary
    if not t:
        return None
    runs = sorted(r for p in PROGRAMS for r in t["modules"].get(p, []))
    if len(runs) < 2:
        return None
    span = runs[-1][0] - runs[0][0]
    busy = sum(b for _, _, b in runs[:-1])
    return 1e3 * (span - busy) / (len(runs) - 1)
