"""`sched.admit_ms.capacity`: mean duration of `sched.admit` (pulling
arrivals, FIFO admission against the pool, prefix-cache lookup) per
loop turn in the traced stretch, ms; its args (requests admitted, their
summed wait) on the earlier line (program_span)."""
from benchmarks import host_spans as hs


def read(ctx, metric):
    found = hs.ring(ctx)
    if found is None:
        return None
    inside, outside, _ = found
    spans = hs.named(inside, "sched.admit")
    admitted = sum(r.args.get("admitted", 0) for r in spans)
    wait_ms = sum(r.args.get("wait_ms", 0.0) for r in spans)
    depth = max((r.args.get("queue_depth", 0) for r in spans), default=0)
    ctx.out(f"sched.admit_ms: {hs.fmt(hs.mean_ms(spans))} over {len(spans)} "
            f"turns in the stretch, {admitted} admitted after "
            f"{wait_ms:.1f} ms of waiting in all, queue at most {depth}; "
            "outside the stretch "
            f"{hs.fmt(hs.mean_ms(hs.named(outside, 'sched.admit')))}")
    return hs.mean_ms(spans)
