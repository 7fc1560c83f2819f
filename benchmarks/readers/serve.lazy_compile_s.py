"""`serve.lazy_compile_s`: seconds in the dispatches that ran a step
program for the first time (`model.enqueue` spans with ``first=1``: the
lazy compile of the decode, prefill and verify programs at the first
requests), each counted as its whole `sched.*.dispatch` span
(program_span)."""
from benchmarks import host_spans as hs


def read(ctx, metric):
    found = hs.ring(ctx)
    if found is None:
        return None
    records = found[2]
    by_id = {r.span_id: r for r in records}
    firsts = [r for r in hs.named(records, "model.enqueue")
              if r.args.get("first")]
    if not firsts:
        return None
    whole = [by_id.get(r.parent_id, r) for r in firsts]
    ctx.out("serve.lazy_compile_s: "
            + " ".join(f"{w.name}={hs.dur(w):.3f}" for w in whole))
    return sum(map(hs.dur, whole))
