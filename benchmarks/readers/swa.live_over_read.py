"""`swa.live_over_read.capacity`: of the ring rows the step programs AS
BUILT read for the window layers (`swa_rows_read`: every slot's whole
ring, a layer), the share that some query of the dispatch sees
(`swa_rows_live`: `min(p + n, window + n - 1)` rows for a row that
advances n tokens from position p), %, summed over the traced
stretch's dispatches of EITHER program (args of `sched.decode.dispatch`
and `sched.prefill.dispatch`, host arithmetic on host-owned lengths;
program_counter).  100 is a read of the live rows alone.  None where the
spans carry no such args (a family without window layers, or the
parent of PR 55)."""
from benchmarks import host_spans as hs

ARGS = ("swa_rows_live", "swa_rows_read")
SPANS = ("sched.decode.dispatch", "sched.prefill.dispatch")


def dispatches(ctx, *args):
    """The stretch's dispatch spans of either program that carry
    `args`, or None."""
    found = hs.ring(ctx)
    if found is None:
        return None
    return [r for r in found[0] if r.name in SPANS
            and all(a in r.args for a in args)] or None


def read(ctx, metric):
    spans = dispatches(ctx, *ARGS)
    if spans is None:
        return None
    live, built = (sum(r.args[a] for r in spans) for a in ARGS)
    if not built:
        return None
    ctx.out(f"swa.live_over_read: {live} ring rows visible to the queries "
            f"of {len(spans)} dispatches, {built} rows read")
    return 100.0 * live / built
