"""`eva.read_hbm_share.capacity`: the EVA read's share of its roofline
in the decode program, %: the least time the chip's memory could take
to read the window and summary rows visible to a decode dispatch's
advancing rows (the family's `eva_read_bytes` of the dispatches' mean
`eva_rows_window` + `eva_rows_summary`, every layer's, keys and values)
at the published bandwidth, over the device time a decode dispatch
spends under the scope `EvaAttention | core`, summed over its launches
(device_trace).  None for a family without `eva_read_bytes`, and where
the stretch ran no such scope."""
from benchmarks import device_scopes as ds
from benchmarks.decode_dispatch import dispatch_args

PROGRAM = "step"


def read(ctx, metric):
    fam = ctx.family
    view = ds.scope_view(ctx)
    if view is None or not hasattr(fam, "eva_read_bytes") or not ctx.peak:
        return None
    rows, per = view
    mine = ds.total(rows, program=PROGRAM, kind="EvaAttention", part="core")
    got = dispatch_args(ctx, "eva_rows_window", "eva_rows_summary")
    if not mine.seconds or not per.get(PROGRAM) or got is None:
        return None
    live = got["eva_rows_window"] + got["eva_rows_summary"]
    core_ms = 1e3 * mine.seconds / per[PROGRAM]
    least_ms = 1e3 * (fam.eva_read_bytes(ctx.cfg, live)
                      / ctx.peak["hbm_bytes_per_s"])
    ctx.out(f"eva.read_hbm_share: least {least_ms:.3f} ms to read "
            f"{live:.0f} live rows against {core_ms:.3f} ms in "
            f"{mine.events / per[PROGRAM]:.0f} instructions of a decode "
            "dispatch under EvaAttention | core")
    return 100.0 * least_ms / core_ms
