"""`step.host_ms`: what a `train_step` costs the host apart from its
wait for room in the runtime's queue, ms: mean duration of the
`train_step` spans in the traced stretch less their `train_step.dispatch`
child; the other children on the earlier line (program_span)."""
from benchmarks import host_spans as hs


def read(ctx, metric):
    found = hs.ring(ctx)
    if found is None:
        return None
    inside, outside, _ = found

    def host_ms(records):
        steps = hs.named(records, "train_step")
        wait = hs.named(records, "train_step.dispatch")
        if not steps:
            return None
        return 1e3 * (sum(map(hs.dur, steps))
                      - sum(map(hs.dur, wait))) / len(steps)

    def pace_ms(steps):
        """Mean time from one `train_step`'s start to the next's."""
        if len(steps) < 2:
            return None
        return 1e3 * (steps[-1].t_start - steps[0].t_start) / (len(steps) - 1)

    inside_steps = hs.named(inside, "train_step")
    after = [r for r in hs.named(outside, "train_step")
             if inside_steps and r.t_start > inside_steps[-1].t_end]
    ctx.out(f"step pace: {hs.fmt(pace_ms(inside_steps))} a step in the "
            f"stretch (profiler open), {hs.fmt(pace_ms(after))} over the "
            f"{len(after)} steps after it (closed)")
    parts = " ".join(
        f"{name}={hs.fmt(hs.mean_ms(hs.named(inside, name)))}"
        for name in ("host_transfer", "train_step.rng_split",
                     "train_step.caches"))
    least = min(map(hs.dur, inside_steps), default=None)
    ctx.out(f"step.host_ms: {hs.fmt(host_ms(inside))} over "
            f"{len(inside_steps)} steps in the stretch ({parts}; the "
            "shortest whole train_step, one that found room in the queue, "
            f"{hs.fmt(least and 1e3 * least)}); outside it (profiler "
            f"closed) {hs.fmt(host_ms(outside))}")
    return host_ms(inside)
