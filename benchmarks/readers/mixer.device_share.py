"""`mixer.device_share.capacity`: of the device's operation time in the
traced stretch, the share under the sequence mixers' op kinds
(`MultiHeadAttention`, `MLAttention`, `GatedDeltaNet`, `ShortConv`), %;
by kind and part (`proj`, `core` or `paged_read`, `conv`, `recurrence`,
`out`) on the earlier line (device_trace).  None where the stretch ran
no such op, and on a tree without the grammar."""
from benchmarks import device_scopes as ds

KINDS = ("MultiHeadAttention", "MLAttention", "GatedDeltaNet", "ShortConv")


def read(ctx, metric):
    view = ds.scope_view(ctx)
    if view is None:
        return None
    whole = ds.total(view[0])
    mine = {k: r for k, r in view[0].items() if k.kind in KINDS}
    if not mine:
        return None
    ctx.out("mixer.device_share by kind and part: " + ", ".join(
        f"{kind} {part or '-'} {ds.share(row, whole):.1f} %"
        for (kind, part), row in ds.grouped(mine, "kind", "part").items()))
    return ds.share(ds.total(mine), whole)
