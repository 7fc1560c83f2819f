"""`idle.unattributed_share.capacity` / `.train`: of the device's idle
time in the traced stretch (the gaps between its operations), the share
during which NO span of the program was open on the dispatching thread,
%: idle time the program's spans cannot explain.  The earlier line
gives the whole split by innermost host span
(`host_spans.idle_by_host_span`; the device plane first shifted as
`host_spans.device_view` says) (device_trace)."""
import json

from benchmarks import host_spans as hs


def read(ctx, metric):
    view = hs.device_view(ctx)
    if view is None:
        return None
    spans, ops, _, _ = view
    split = hs.idle_by_host_span(spans, ops)
    idle = sum(split.values())
    ctx.out("idle_by_host_span " + json.dumps(
        {k: round(v, 6) for k, v in sorted(split.items(),
                                           key=lambda kv: -kv[1])}))
    if not idle:
        return None
    return 100.0 * split.get(hs.OUTSIDE, 0.0) / idle
