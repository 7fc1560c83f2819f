"""`sched.ahead_share.capacity`: of the traced stretch's sampling
dispatches (`sampling_dispatch.dispatches`: a `sched.decode.dispatch`
span, or a `sched.prefill.dispatch` span that carries `decode_rows`),
the share that was enqueued BEFORE the fetch of the sampling dispatch
before it had returned, %: their `ahead` arg, 1 or 0 (program_span;
ISSUE 54: one dispatch of lookahead, the host's turn beside a pass and
not between two).  0.0 for a family whose programs leave no ids on the
device (GPT's scan and step: every dispatch is fetched at once).  None
where no sampling dispatch of the stretch says `ahead` (the parent of
PR 54) or the stretch dispatched none."""
from benchmarks import sampling_dispatch as sd


def read(ctx, metric):
    spans = [r for r in sd.dispatches(ctx) or () if "ahead" in r.args]
    if not spans:
        return None
    ahead = sum(r.args["ahead"] for r in spans)
    ctx.out(f"sched.ahead_share: {ahead} of {len(spans)} sampling "
            f"dispatches ({sd.by_program(spans)}) were enqueued behind an "
            "unfetched one")
    return 100.0 * ahead / len(spans)
