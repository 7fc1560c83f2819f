"""`sched.sample_ms.capacity`: mean duration of `sched.sample` (argmax
per row on the fetched logits, per-row bookkeeping, retiring finished
requests) per decode dispatch in the traced stretch, ms (program_span)."""
from benchmarks import host_spans as hs


def read(ctx, metric):
    found = hs.ring(ctx)
    if found is None:
        return None
    inside, outside, _ = found
    spans = hs.named(inside, "sched.sample")
    ctx.out(f"sched.sample_ms: {hs.fmt(hs.mean_ms(spans))} over "
            f"{len(spans)} decode dispatches in the stretch, "
            f"{sum(r.args.get('tokens', 0) for r in spans)} tokens and "
            f"{sum(r.args.get('finished', 0) for r in spans)} requests "
            "finished; outside the stretch "
            f"{hs.fmt(hs.mean_ms(hs.named(outside, 'sched.sample')))}")
    return hs.mean_ms(spans)
