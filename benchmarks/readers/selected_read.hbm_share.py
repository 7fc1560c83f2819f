"""`selected_read.hbm_share.capacity`: the selected read's share of its
roofline, %: the least time the chip's memory could take to read a
dispatch's PICKED latents once a layer (the family's
`selected_read_bytes` of the dispatches' mean `dsa_keys_selected`:
`min(t + 1, index_topk)` rows of rank + rope values a real query a
layer) at the published bandwidth, over the device time a dispatch
spends under the scope `MLAttention | selected_read` (the gather and
the attention over the gathered rows), in EITHER step program
(device_trace).  None for a family without `selected_read_bytes` and
where the stretch ran no such scope."""
from benchmarks import device_scopes as ds
from benchmarks import host_spans as hs

PROGRAMS = {"sched.decode.dispatch": "step",
            "sched.prefill.dispatch": "prefill"}


def read(ctx, metric):
    fam = ctx.family
    view, found = ds.scope_view(ctx), hs.ring(ctx)
    if (view is None or found is None or not ctx.peak
            or not hasattr(fam, "selected_read_bytes")):
        return None
    rows, per = view
    spans = [r for r in found[0] if r.name in PROGRAMS
             and "dsa_keys_selected" in r.args]
    programs = tuple({PROGRAMS[r.name] for r in spans})
    mine = ds.total(rows, program=programs, kind="MLAttention",
                    part="selected_read")
    runs = sum(per.get(p, 0) for p in programs)
    if not spans or not mine.seconds or not runs:
        return None
    picked = sum(r.args["dsa_keys_selected"] for r in spans) / len(spans)
    read_ms = 1e3 * mine.seconds / runs
    least_ms = 1e3 * (fam.selected_read_bytes(ctx.cfg, picked)
                      / ctx.peak["hbm_bytes_per_s"])
    ctx.out(f"selected_read.hbm_share: least {least_ms:.3f} ms to read "
            f"{picked:.0f} picked latents a layer against {read_ms:.3f} ms "
            f"a dispatch under selected_read ({runs} runs of "
            f"{'+'.join(programs)})")
    return 100.0 * least_ms / read_ms
