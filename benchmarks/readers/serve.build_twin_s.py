"""`serve.build_twin_s`: seconds in `serve.build_twin` (per replica: the
decode graph, its compile, the weights' copy, state and pool), summed
over the replicas built; children on the earlier line (program_span)."""
from benchmarks import host_spans as hs


def read(ctx, metric):
    found = hs.ring(ctx)
    if found is None:
        return None
    records = found[2]
    twins = hs.named(records, "serve.build_twin")
    if not twins:
        return None
    fronts = hs.named(records, "serve.build_front")
    ctx.out("serve.build_front "
            + " ".join(f"{hs.dur(f):.3f} s {f.args}" for f in fronts)
            + "; twins " + " ".join(f"{hs.dur(t):.3f} s {t.args}"
                                    for t in twins)
            + "; serve.copy_weights "
            + " ".join(f"{hs.dur(c):.3f}"
                       for c in hs.named(records, "serve.copy_weights")))
    return sum(map(hs.dur, twins))
