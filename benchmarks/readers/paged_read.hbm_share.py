"""`paged_read.hbm_share.capacity`: the paged attention read's share of
its roofline, %: the least time the chip's memory could take to read
the live pages of a decode dispatch (the family's `paged_read_bytes` of
the dispatches' mean `kv_blocks_live`: every layer's page in every
plane) at the published bandwidth, over the device time a decode
dispatch spends under the scope `MultiHeadAttention | paged_read`
(the pool's scatter and the read, Pallas kernel or gather), summed over
its launches (device_trace).  None for a family without
`paged_read_bytes`, and where the stretch ran no such scope."""
from benchmarks import device_scopes as ds
from benchmarks.decode_dispatch import dispatch_args

PROGRAM = "step"


def read(ctx, metric):
    fam = ctx.family
    view = ds.scope_view(ctx)
    if view is None or not hasattr(fam, "paged_read_bytes") or not ctx.peak:
        return None
    rows, per = view
    mine = ds.total(rows, program=PROGRAM, kind="MultiHeadAttention",
                    part="paged_read")
    got = dispatch_args(ctx, "kv_blocks_live")
    if not mine.seconds or not per.get(PROGRAM) or got is None:
        return None
    read_ms = 1e3 * mine.seconds / per[PROGRAM]
    least_ms = 1e3 * (fam.paged_read_bytes(ctx.cfg, got["kv_blocks_live"])
                      / ctx.peak["hbm_bytes_per_s"])
    ctx.out(f"paged_read.hbm_share: least {least_ms:.3f} ms to read "
            f"{got['kv_blocks_live']:.0f} live blocks against "
            f"{read_ms:.3f} ms in {mine.events / per[PROGRAM]:.0f} "
            "instructions of a decode dispatch under paged_read")
    return 100.0 * least_ms / read_ms
