"""`kda.device_share.train`: of the device's operation time in the
traced stretch, the share under op kind `KimiDeltaAttention`, every
phase, %; by part (`proj`, `conv`, `gate`, `core`, `norm_gate`, `out`)
and phase on the earlier line (device_trace).  None where the stretch
ran no such op, and on a tree without the grammar or the op."""
from benchmarks import device_scopes as ds

KIND = "KimiDeltaAttention"


def read(ctx, metric):
    view = ds.scope_view(ctx)
    if view is None:
        return None
    rows = view[0]
    mine = {k: r for k, r in rows.items() if k.kind == KIND}
    if not mine:
        return None
    whole = ds.total(rows)
    ctx.out("kda.device_share by part and phase: " + ", ".join(
        f"{part or '-'} {phase or '-'} {ds.share(row, whole):.1f} %"
        for (part, phase), row in ds.grouped(mine, "part", "phase").items()))
    return ds.share(ds.total(mine), whole)
