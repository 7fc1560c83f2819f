"""`experts.products_share.train` / `.capacity`: of the device time
under op kind `RoutedExperts`, the share in its parts `products` (the
ragged dots or the dense einsums) and `shared` (the shared expert), %;
the rest is `route`, `dispatch` (sort, permutations, gathers, masks),
`combine` and the weights' casts, by part on the earlier line
(device_trace).  None where the stretch ran no such op, and on a tree
without the grammar."""
from benchmarks import device_scopes as ds

KIND = "RoutedExperts"


def read(ctx, metric):
    view = ds.scope_view(ctx)
    if view is None:
        return None
    mine = {k: r for k, r in view[0].items() if k.kind == KIND}
    whole = ds.total(mine)
    if not whole.events:
        return None
    ctx.out("experts.products_share by part: " + ", ".join(
        f"{part or '-'} {ds.share(row, whole):.1f} %"
        for (part,), row in ds.grouped(mine, "part").items()))
    return ds.share(ds.total(mine, part=("products", "shared")), whole)
