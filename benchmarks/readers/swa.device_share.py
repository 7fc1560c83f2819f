"""`swa.device_share.capacity`: of the device's operation time in the
traced stretch, the share under `MultiHeadAttention | window_read` (a
window layer's ring: the step's rows written, the whole ring read under
the window's mask), %, in either step program; by program on the
earlier line, beside the full layers' `paged_read` (device_trace).
None where the stretch ran no such scope, and on a tree without the
part."""
from benchmarks import device_scopes as ds

KIND, PART = "MultiHeadAttention", "window_read"


def read(ctx, metric):
    view = ds.scope_view(ctx)
    if view is None:
        return None
    whole = ds.total(view[0])
    mine = {k: r for k, r in view[0].items()
            if k.kind == KIND and k.part == PART}
    if not mine:
        return None
    paged = ds.total(view[0], kind=KIND, part="paged_read")
    ctx.out("swa.device_share by program: " + ", ".join(
        f"{program} {ds.share(row, whole):.1f} %"
        for (program,), row in ds.grouped(mine, "program").items())
        + f"; the full layers' paged_read {ds.share(paged, whole):.1f} %")
    return ds.share(ds.total(mine), whole)
