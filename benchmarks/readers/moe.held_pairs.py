"""`moe.held_pairs.capacity`: routed (token, expert) pairs that landed
on the experts held here, per decode dispatch, summed over the routed
layers: mean over the traced stretch of the `moe_pairs` arg of
`sched.decode.dispatch` (program_counter).  Beside it on an earlier
line: pairs the combine left out (`moe_dropped`, which has to read 0)
and the held experts that received a row (`moe_hit`)."""
from benchmarks.decode_dispatch import dispatch_args


def read(ctx, metric):
    got = dispatch_args(ctx, "moe_pairs", "moe_dropped", "moe_hit")
    if got is None:
        return None
    ctx.out(f"moe.held_pairs: {got['moe_pairs']:.2f} pairs on held "
            f"experts, {got['moe_dropped']:.3g} dropped, "
            f"{got['moe_hit']:.2f} held experts hit, a decode dispatch "
            "(summed over the routed layers)")
    return got["moe_pairs"]
