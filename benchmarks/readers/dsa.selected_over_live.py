"""`dsa.selected_over_live.capacity`: of the keys a dense read would
attend for the stretch's REAL tokens (`dsa_keys_live`: `t + 1` a query
at position t), the share the selection attends (`dsa_keys_selected`:
`min(t + 1, index_topk)`), %, summed over the traced stretch's
dispatches of EITHER program (args of `sched.decode.dispatch` and
`sched.prefill.dispatch`, host arithmetic on host-owned lengths;
program_counter).  100 = the traffic never left the dense regime.  None
where the spans carry no such args (a family whose attention reads
every key, or the parent of PR 57)."""
from benchmarks import host_spans as hs

ARGS = ("dsa_keys_selected", "dsa_keys_live")
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
    spans = dispatches(ctx, *ARGS, "dsa_rows_past_topk")
    if spans is None:
        return None
    selected, live = (sum(r.args[a] for r in spans) for a in ARGS)
    if not live:
        return None
    past = sum(r.args["dsa_rows_past_topk"] for r in spans)
    rows = sum(r.args.get("rows", 0) + r.args.get("feeding", 0)
               for r in spans)
    ctx.out(f"dsa.selected_over_live: {selected} keys attended of {live} "
            f"live, a layer, over {len(spans)} dispatches; {past} of "
            f"{rows} rows had a query past index_topk")
    return 100.0 * selected / live
