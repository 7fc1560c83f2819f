"""`flash.roofline_share.train`: the long-row flash-attention kernels'
share of the bf16 peak, %: the causal FLOPs their forward and backward
need a step (the family's `attention_core_flops`: 2 products forward, 5
backward; compute-bound at d = 64 and thousands of keys) over the peak,
over their summed device time a step, from the traced stretch's events
by kernel name (device_trace).  None where the trace holds no such
kernel (a step whose attention takes another core, or the parent of
PR 36, whose kernels carry no name)."""
from benchmarks import host_spans as hs
from benchmarks.reduce_trace import stem

KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
           "flash_attention_bwd_dkv")


def read(ctx, metric):
    steps = ctx.counters.get("traced_steps")
    fam = ctx.family
    if not steps or not ctx.peak or not hasattr(fam, "attention_core_flops"):
        return None
    view = hs.device_view(ctx)  # the first chip's operations, read once
    if view is None:
        return None
    seconds = {k: 0.0 for k in KERNELS}
    for name, start, end in view[1]:
        if stem(name) in seconds:
            seconds[stem(name)] += end - start
    total = sum(seconds.values())
    if not total:
        return None
    flops = fam.attention_core_flops(
        ctx.cfg, ctx.traffic["batch_per_chip"], ctx.traffic["seq"])
    least_s = flops / ctx.peak["bf16_flops_per_s"]
    ctx.out("flash.roofline_share: " + ", ".join(
        f"{k} {1e3 * v / steps:.3f} ms" for k, v in seconds.items())
        + f" a step; least {1e3 * least_s:.3f} ms at the bf16 peak")
    return 100.0 * least_s / (total / steps)
