"""Device busy time of one train step, ms: the busy union of the
traced stretch over the steps dispatched in it (device_trace)."""


def read(ctx, metric):
    t, steps = ctx.trace_summary, ctx.counters.get("traced_steps")
    if not t or not steps:
        return None
    return 1e3 * t["busy_s"] / steps
