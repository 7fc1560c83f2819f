"""`ssm.device_share.capacity`: of the device's operation time in the
traced stretch, the share under the state-space mixers' scopes
(`Mamba2Mixer:*`, `ops/mamba2.py`), %, in either step program; by part
(`proj` the input projection and the step sizes, `conv`, `recurrence`
the chunk's terms and the state's read and write, `out` the gated norm
and the output projection) on the earlier line, and on the line before
it the attention layers' parts, the other half of such a model's pass
(`mixer.device_share.capacity` does not list this cell: its reader
does not know the kind) (device_trace).  The mechanism's share of the
cell.  None where the stretch ran no such op, and on a tree without
it."""
from benchmarks import device_scopes as ds

KIND, BESIDE = "Mamba2Mixer", "MultiHeadAttention"


def read(ctx, metric):
    view = ds.scope_view(ctx)
    if view is None:
        return None
    whole = ds.total(view[0])
    mine = {k: r for k, r in view[0].items() if k.kind == KIND}
    if not mine:
        return None
    beside = {k: r for k, r in view[0].items() if k.kind == BESIDE}
    ctx.out(f"ssm.device_share beside {BESIDE}: " + ", ".join(
        f"{part or '-'} {ds.share(row, whole):.1f} %"
        for (part,), row in ds.grouped(beside, "part").items()))
    ctx.out("ssm.device_share by part: " + ", ".join(
        f"{part or '-'} {ds.share(row, whole):.1f} %"
        for (part,), row in ds.grouped(mine, "part").items()))
    return ds.share(ds.total(mine), whole)
