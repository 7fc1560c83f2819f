"""Share of the bf16 peak one train step reaches on the device: the
FLOPs the forward and backward passes need (the family's own function
of the shapes; recomputation never counts) over the peak, over the
step's device busy time from the trace.  Compute-bound by construction:
the step's bytes (weights, Adam state, activations once) need far less
time than its FLOPs (device_trace)."""


def read(ctx, metric):
    t, steps = ctx.trace_summary, ctx.counters.get("traced_steps")
    if not t or not steps or not ctx.peak:
        return None
    flops = ctx.family.train_flops_per_step(
        ctx.cfg, ctx.traffic["batch_per_chip"], ctx.traffic["seq"])
    least_s = flops / ctx.peak["bf16_flops_per_s"]
    return 100.0 * least_s / (t["busy_s"] / steps)
