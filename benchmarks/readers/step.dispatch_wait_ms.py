"""`step.dispatch_wait_ms`: mean duration of `train_step.dispatch` in
the traced stretch, ms: the enqueue of the jitted step and, while the
host is as far ahead of the device as the runtime lets it, the wait for
room in its queue (about a step's time then; near 0 would mean the host
sets the pace) (program_span)."""
from benchmarks import host_spans as hs


def read(ctx, metric):
    found = hs.ring(ctx)
    if found is None:
        return None
    inside, outside, _ = found
    spans = hs.named(inside, "train_step.dispatch")
    ctx.out(f"step.dispatch_wait_ms: {hs.fmt(hs.mean_ms(spans))} over "
            f"{len(spans)} steps in the stretch; outside it "
            + hs.fmt(hs.mean_ms([
                r for r in hs.named(outside, "train_step.dispatch")
                if hs.dur(r) < 1.0])) + " (compiling calls left out)")
    return hs.mean_ms(spans)
