"""`moe_train.load_max_over_mean`: rows of the fullest held expert over
the rows of the mean held expert, per routed layer, over the traced
stretch's steps: `moe_max_rows` x experts held / `moe_pairs`, both
summed over layers and steps (program_counter).  1 is an even load."""
from benchmarks.moe_train_counts import counts


def read(ctx, metric):
    got = counts(ctx)
    if got is None or not got["pairs"]:
        return None
    return got["max_rows"] * ctx.cfg["num_experts"] / got["pairs"]
