"""`recompute.device_ms`: device time a step in the `recompute` phase
(jax's `checkpoint/rematted_computation`: what a checkpointed segment
runs a second time for its backward pass), ms; the earlier line gives
it by op kind (device_trace).  None where the step has no checkpointed
segment, and on a tree without the grammar."""
from benchmarks import device_scopes as ds


def read(ctx, metric):
    view = ds.scope_view(ctx)
    if view is None or not view[1].get("step"):
        return None
    rows, per = view
    mine = {k: r for k, r in rows.items() if k.phase == "recompute"}
    if not mine:
        return None
    ctx.out("recompute.device_ms by kind: " + ", ".join(
        f"{kind} {1e3 * row.seconds / per['step']:.3f} ms"
        for (kind,), row in ds.grouped(mine, "kind").items()))
    return 1e3 * ds.total(mine).seconds / per["step"]
