"""`optimizer.device_ms`: device time a step under the `optimizer`
scope (`executor.build_step` around the update, the ZeRO wrappers
inside), every `hlo_category`, ms; by category on the earlier line.
XLA fuses Adam's update into the weight-gradient matmuls and a fusion
has one `tf_op`: on the v5e such a ``convolution fusion`` carries its
PRODUCT's, so this reads the update XLA did NOT fuse (``loop fusion``),
and the fused part lies in the layers' ``backward | convolution
fusion`` rows of the table (`device_scopes.py` "The limit")
(device_trace).  None on a tree without the grammar."""
from benchmarks import device_scopes as ds


def read(ctx, metric):
    view = ds.scope_view(ctx)
    if view is None or not view[1].get("step"):
        return None
    rows, per = view
    mine = {k: r for k, r in rows.items() if k.kind == "optimizer"}
    if not mine:
        return None
    ctx.out("optimizer.device_ms by hlo_category: " + ", ".join(
        f"{category or '-'} {1e3 * row.seconds / per['step']:.3f} ms "
        f"({row.flops / per['step'] / 1e12:.3f} TFLOP)"
        for (category,), row in ds.grouped(mine, "category").items()))
    return 1e3 * ds.total(mine).seconds / per["step"]
