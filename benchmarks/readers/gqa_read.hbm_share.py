"""`gqa_read.hbm_share.capacity`: the grouped in-place paged read's
share of its roofline, %: the least time the chip's memory could take
to read the live pages of a dispatch (the family's `paged_read_bytes`
of the dispatches' mean `kv_blocks_live`: the full layers' pages) at
the published bandwidth, over the device time a dispatch spends under
the scope `MultiHeadAttention | paged_read` (the pool's scatter and the
read), in EITHER step program (device_trace).  None for a family
without `paged_read_bytes`, where the stretch ran no such scope, and
where the dispatches read the table's width and not the live pages
(`kv_blocks_read` != `kv_blocks_live`: the gather, which is no
in-place read)."""
from benchmarks import device_scopes as ds
from benchmarks import host_spans as hs

PROGRAMS = {"sched.decode.dispatch": "step",
            "sched.prefill.dispatch": "prefill"}


def read(ctx, metric):
    fam = ctx.family
    view = ds.scope_view(ctx)
    found = hs.ring(ctx)
    if (view is None or found is None or not ctx.peak
            or not hasattr(fam, "paged_read_bytes")):
        return None
    rows, per = view
    spans = [r for r in found[0] if r.name in PROGRAMS
             and "kv_blocks_live" in r.args and "kv_blocks_read" in r.args]
    if not spans or any(r.args["kv_blocks_read"] != r.args["kv_blocks_live"]
                        for r in spans):
        return None
    programs = tuple({PROGRAMS[r.name] for r in spans})
    mine = ds.total(rows, program=programs, kind="MultiHeadAttention",
                    part="paged_read")
    runs = sum(per.get(p, 0) for p in programs)
    if not mine.seconds or not runs:
        return None
    live = sum(r.args["kv_blocks_live"] for r in spans) / len(spans)
    read_ms = 1e3 * mine.seconds / runs
    least_ms = 1e3 * (fam.paged_read_bytes(ctx.cfg, live)
                      / ctx.peak["hbm_bytes_per_s"])
    ctx.out(f"gqa_read.hbm_share: least {least_ms:.3f} ms to read "
            f"{live:.0f} live blocks against {read_ms:.3f} ms a dispatch "
            f"under paged_read ({runs} runs of {'+'.join(programs)})")
    return 100.0 * least_ms / read_ms
