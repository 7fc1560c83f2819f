"""`rstate.touched_over_live.capacity`: rows whose recurrent state the
step programs read and wrote over rows whose state the pass had to
advance, summed over the traced stretch's decode and prefill dispatches
(`rstate_rows_touched` / `rstate_rows_live`, args of
`sched.decode.dispatch` and `sched.prefill.dispatch`; program_counter).
1.0 is the floor: an update of the live rows alone.  The plain
recurrence runs over every slot and masks the rest, so a prefill
dispatch that advances 3 of 64 rows reads 21.  None where the program's
spans carry no such args (a family without recurrent state, or the
parent of PR 34)."""
from benchmarks import host_spans as hs

ARGS = ("rstate_rows_live", "rstate_rows_touched")


def dispatches(ctx):
    """The stretch's decode and prefill dispatch spans that carry the
    recurrent-state args, or None."""
    found = hs.ring(ctx)
    if found is None:
        return None
    spans = [r for name in ("sched.decode.dispatch",
                            "sched.prefill.dispatch")
             for r in hs.named(found[0], name)
             if all(a in r.args for a in ARGS)]
    return spans or None


def read(ctx, metric):
    spans = dispatches(ctx)
    if spans is None:
        return None
    live = sum(r.args["rstate_rows_live"] for r in spans)
    touched = sum(r.args["rstate_rows_touched"] for r in spans)
    if not live:
        return None
    ctx.out(f"rstate.touched_over_live: {touched} rows' state read and "
            f"written for {live} rows advanced, over {len(spans)} decode "
            "and prefill dispatches")
    return touched / live
