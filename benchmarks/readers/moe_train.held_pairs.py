"""`moe_train.held_pairs`: routed (token, expert) pairs that landed on
the experts held here, a train step, summed over the routed layers:
mean of the `moe_pairs` arg of the stretch's `train_step.moe` spans
(program_counter).  On the earlier line: pairs the product left out
(`moe_dropped`, which has to read 0) and the held experts that received
a row a layer."""
from benchmarks.moe_train_counts import counts


def read(ctx, metric):
    got = counts(ctx)
    if got is None:
        return None
    ctx.out(f"moe_train.held_pairs: {got['pairs'] / got['steps']:.1f} "
            f"pairs on held experts a step, {got['dropped']} dropped in "
            f"{got['steps']} steps, {got['hit'] / got['steps']:.2f} held "
            "experts hit a step (summed over the routed layers)")
    return got["pairs"] / got["steps"]
