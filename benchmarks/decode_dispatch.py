"""Means of the args the scheduler puts on `sched.decode.dispatch`, over
the decode dispatches of the traced stretch (from the span ring:
program_counter).  Shared by the readers that PR 29 added."""
from benchmarks import host_spans as hs


def dispatch_args(ctx, *names):
    """{name: mean over the stretch's decode dispatches} or None where
    the program's spans carry no such args (the parent of PR 29)."""
    found = hs.ring(ctx)
    if found is None:
        return None
    spans = [r for r in hs.named(found[0], "sched.decode.dispatch")
             if all(n in r.args for n in names)]
    if not spans:
        return None
    return {n: sum(r.args[n] for r in spans) / len(spans) for n in names}
