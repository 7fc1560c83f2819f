"""`ssm.state_hbm_share.capacity`: the Mamba-2 recurrence's share of its
roofline, %: the least time the chip's memory could take to read AND
write the state-space state of the rows a dispatch advances (2 x the
dispatches' mean `rstate_rows_live` x the family's `ssm_state_bytes` a
row: `[H, P, N]` float32 a Mamba layer; from the spans' args, never
from padded shapes) at the published bandwidth, over the device time a
dispatch spends under the scope `Mamba2Mixer | recurrence`, in EITHER
step program (device_trace).  It reads the same work whatever
implements it (the plain form touches every slot and more than once,
the kernel the live rows once each way), so it cannot pass 100.  None
for a family without `ssm_state_bytes`, where the stretch ran no such
scope, and where the spans carry no `rstate_rows_live`."""
from benchmarks import device_scopes as ds
from benchmarks import host_spans as hs

PROGRAMS = {"sched.decode.dispatch": "step",
            "sched.prefill.dispatch": "prefill"}


def read(ctx, metric):
    fam = ctx.family
    view = ds.scope_view(ctx)
    found = hs.ring(ctx)
    if (view is None or found is None or not ctx.peak
            or not hasattr(fam, "ssm_state_bytes")):
        return None
    rows, per = view
    spans = [r for r in found[0] if r.name in PROGRAMS
             and "rstate_rows_live" in r.args]
    if not spans:
        return None
    programs = tuple({PROGRAMS[r.name] for r in spans})
    mine = ds.total(rows, program=programs, kind="Mamba2Mixer",
                    part="recurrence")
    runs = sum(per.get(p, 0) for p in programs)
    if not mine.seconds or not runs:
        return None
    live = sum(r.args["rstate_rows_live"] for r in spans) / len(spans)
    took_ms = 1e3 * mine.seconds / runs
    least_ms = 1e3 * (2.0 * fam.ssm_state_bytes(ctx.cfg, live)
                      / ctx.peak["hbm_bytes_per_s"])
    ctx.out(f"ssm.state_hbm_share: least {least_ms:.3f} ms to read and "
            f"write {live:.1f} live rows' state against {took_ms:.3f} ms a "
            f"dispatch under Mamba2Mixer | recurrence ({runs} runs of "
            f"{'+'.join(programs)})")
    return 100.0 * least_ms / took_ms
