"""`eva.device_share.capacity`: of the device's operation time in the
traced stretch, the share under the op kind `EvaAttention`, %; by part
(`proj`, `summarise`, `core`, `state_write`, `out`) on the earlier line
(device_trace).  None where the stretch ran no such op, and on a tree
without the kind."""
from benchmarks import device_scopes as ds

KIND = "EvaAttention"


def read(ctx, metric):
    view = ds.scope_view(ctx)
    if view is None:
        return None
    whole = ds.total(view[0])
    mine = {k: r for k, r in view[0].items() if k.kind == KIND}
    if not mine:
        return None
    ctx.out("eva.device_share by program and part: " + ", ".join(
        f"{program} {part or '-'} {ds.share(row, whole):.1f} %"
        for (program, part), row in ds.grouped(mine, "program",
                                               "part").items()))
    return ds.share(ds.total(mine), whole)
