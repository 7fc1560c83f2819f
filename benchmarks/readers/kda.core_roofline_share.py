"""`kda.core_roofline_share.train`: the delta rule's cores against
their floor, %: the least time the chip could take for what the cores of
a step need (the family's `kda_core_flops` at the bf16 peak or its
`kda_core_bytes` at the published bandwidth, whichever is longer: both
counted from the configuration alone, never from a chunk length, and
without a forward run twice) over the device time a step under
`KimiDeltaAttention`'s `core` scope, every phase (so a recomputed
forward counts against the share), whatever implements the core
(device_trace).  None where the stretch ran no such scope, for a family
without the two functions, and on a tree without the grammar."""
from benchmarks import device_scopes as ds

KIND, PART = "KimiDeltaAttention", "core"


def read(ctx, metric):
    fam = ctx.family
    if not ctx.peak or not all(hasattr(fam, f) for f in (
            "kda_core_flops", "kda_core_bytes")):
        return None
    view = ds.scope_view(ctx)
    if view is None or not view[1].get("step"):
        return None
    rows, per = view
    mine = {k: r for k, r in rows.items()
            if k.kind == KIND and k.part == PART}
    if not mine:
        return None
    seconds = ds.total(mine).seconds / per["step"]
    shape = (ctx.cfg, ctx.traffic["batch_per_chip"], ctx.traffic["seq"])
    by_flops = fam.kda_core_flops(*shape) / ctx.peak["bf16_flops_per_s"]
    by_bytes = fam.kda_core_bytes(*shape) / ctx.peak["hbm_bytes_per_s"]
    ctx.out("kda.core_roofline_share: " + ", ".join(
        f"{phase or '-'} {1e3 * row.seconds / per['step']:.3f} ms"
        for (phase,), row in ds.grouped(mine, "phase").items())
        + f" a step; least {1e3 * by_flops:.3f} ms by operations at the "
          f"bf16 peak, {1e3 * by_bytes:.3f} ms by bytes")
    return 100.0 * max(by_flops, by_bytes) / seconds
