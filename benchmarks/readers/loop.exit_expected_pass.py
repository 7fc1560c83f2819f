"""`loop.exit_expected_pass.capacity`: the pass after which a row would
leave if the exit gate were acted on, in expectation: `1 + sum_t t x
exit_mass_t`, from the `exit_mass_<t>` args of `sched.decode.dispatch`
(the exit pdf after pass t, mean over the dispatch's live rows), mean
over the traced stretch's decode dispatches (program_counter).  Between
1 and the region's passes; the passes less this is what acting on the
gate could save of a decode dispatch's weight reads."""
from benchmarks import host_spans as hs


def read(ctx, metric):
    found = hs.ring(ctx)
    if found is None:
        return None
    spans = [r for r in hs.named(found[0], "sched.decode.dispatch")
             if "exit_mass_0" in r.args]
    if not spans:
        return None
    passes = 0
    while f"exit_mass_{passes}" in spans[0].args:
        passes += 1
    mass = [sum(r.args[f"exit_mass_{t}"] for r in spans) / len(spans)
            for t in range(passes)]
    ctx.out("loop.exit_expected_pass: exit pdf by pass "
            + " ".join(f"{m:.4f}" for m in mass)
            + f", mean over {len(spans)} decode dispatches")
    return 1.0 + sum(t * m for t, m in enumerate(mass))
