"""`eva.live_over_read.capacity`: of the window and summary rows the
decode program AS BUILT reads for the EVA layers (`eva_rows_read`:
every slot's whole window and store, padded shapes and all), the share
the advancing rows' queries could see (`eva_rows_window` +
`eva_rows_summary`), %, summed over the traced stretch's decode
dispatches (args of `sched.decode.dispatch`, host arithmetic on
host-owned lengths; program_counter).  100 is a read of the live rows
alone.  None where the program's spans carry no such args (a family
without EVA layers, or the parent of PR 51)."""
from benchmarks import host_spans as hs

ARGS = ("eva_rows_window", "eva_rows_summary", "eva_rows_read")


def decode_dispatches(ctx):
    """The stretch's decode dispatch spans that carry the EVA args, or
    None."""
    found = hs.ring(ctx)
    if found is None:
        return None
    spans = [r for r in hs.named(found[0], "sched.decode.dispatch")
             if all(a in r.args for a in ARGS)]
    return spans or None


def read(ctx, metric):
    spans = decode_dispatches(ctx)
    if spans is None:
        return None
    window, summary, built = (sum(r.args[a] for r in spans) for a in ARGS)
    if not built:
        return None
    ctx.out(f"eva.live_over_read: {window + summary} live rows "
            f"({window} of windows, {summary} summaries) "
            f"of {built} read over {len(spans)} decode dispatches")
    return 100.0 * (window + summary) / built
