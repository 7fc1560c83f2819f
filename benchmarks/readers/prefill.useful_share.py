"""`prefill.useful_share.capacity`: real prompt tokens advanced over the
``slots x chunk`` positions a chunked-prefill dispatch computes, %, over
the prefill dispatches of the traced stretch (args of
`sched.prefill.dispatch`): every slot rides every prefill dispatch
(program_counter)."""
from benchmarks import host_spans as hs


def read(ctx, metric):
    found = hs.ring(ctx)
    if found is None:
        return None
    spans = hs.named(found[0], "sched.prefill.dispatch")
    capacity = sum(r.args["capacity"] for r in spans)
    if not capacity:
        return None
    tokens = sum(r.args["tokens"] for r in spans)
    ctx.out(f"prefill.useful_share: {tokens} prompt tokens in "
            f"{sum(r.args['rows'] for r in spans)} rows of {capacity} "
            f"positions computed by {len(spans)} prefill dispatches")
    return 100.0 * tokens / capacity
