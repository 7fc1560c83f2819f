"""`decode.rows.capacity`: mean number of rows past their prompt (the
rows whose logits are sampled) per decode dispatch in the traced
stretch, from the `rows` arg of `sched.decode.dispatch`; at most the
slots (program_counter)."""
from benchmarks import host_spans as hs


def read(ctx, metric):
    found = hs.ring(ctx)
    if found is None:
        return None
    spans = hs.named(found[0], "sched.decode.dispatch")
    if not spans:
        return None
    rows = sum(r.args["rows"] for r in spans) / len(spans)
    feeding = sum(r.args["feeding"] for r in spans) / len(spans)
    ctx.out(f"decode.rows: {rows:.3f} rows in decode and {feeding:.3f} "
            f"still feeding their prompt of {spans[0].args['slots']} slots, "
            f"mean over {len(spans)} decode dispatches")
    return rows
