"""`moe.load_max_over_mean.capacity`: rows of the fullest held expert
over the rows of the mean held expert, per routed layer, over the
traced stretch's decode dispatches: `moe_max_rows` / (`moe_pairs` /
experts held), both summed over layers (program_counter).  1 is an even
load; a static-shape dispatch has to size every expert for the
fullest."""
from benchmarks.decode_dispatch import dispatch_args


def read(ctx, metric):
    got = dispatch_args(
        ctx, "moe_pairs", "moe_max_rows")
    if got is None or not got["moe_pairs"]:
        return None
    return got["moe_max_rows"] * ctx.cfg["n_routed_experts"] \
        / got["moe_pairs"]
