"""`experts.device_share.train` / `.capacity`: of the device's operation
time in the traced stretch, the share under op kind `RoutedExperts`,
every phase and part, %: "experts against mixers" (device_trace).  None
where the stretch ran no such op, and on a tree without the grammar."""
from benchmarks import device_scopes as ds

KIND = "RoutedExperts"


def read(ctx, metric):
    view = ds.scope_view(ctx)
    if view is None:
        return None
    rows = view[0]
    mine = ds.total(rows, kind=KIND)
    return ds.share(mine, ds.total(rows)) if mine.events else None
