"""`collective.exposed_ms`: time a train step spends in collectives
while no compute runs on that device, ms a step: the trace summary's
`collective_exposed_s` (a chip's mean over the traced stretch) over the
steps dispatched in it (device_trace).  One chip has no collectives and
reads 0."""


def read(ctx, metric):
    t, steps = ctx.trace_summary, ctx.counters.get("traced_steps")
    if not t or not steps or "collective_exposed_s" not in t:
        return None
    return 1e3 * t["collective_exposed_s"] / steps
