"""`dsa.device_share.capacity`: of the device's operation time in the
traced stretch, the share under the selection's scopes of `MLAttention`
(`ops/mla.py` "Selected keys"): the indexer's `index_proj`,
`index_scores` and `topk` in the `full` layers and the `selected_read`
(the picked latents' gather and the attention over them) in every
layer, %, in either step program; by part on the earlier line
(device_trace).  The mechanism's share of the cell.  None where the
stretch ran no such scope, and on a tree without the parts."""
from benchmarks import device_scopes as ds

KIND = "MLAttention"
PARTS = ("index_proj", "index_scores", "topk", "selected_read")


def read(ctx, metric):
    view = ds.scope_view(ctx)
    if view is None:
        return None
    whole = ds.total(view[0])
    mine = {k: r for k, r in view[0].items()
            if k.kind == KIND and k.part in PARTS}
    if not mine:
        return None
    ctx.out("dsa.device_share by part: " + ", ".join(
        f"{part} {ds.share(row, whole):.1f} %"
        for (part,), row in ds.grouped(mine, "part").items()))
    return ds.share(ds.total(mine), whole)
