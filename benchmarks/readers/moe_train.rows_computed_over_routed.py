"""`moe_train.rows_computed_over_routed`: rows the routed layers'
expert product multiplied over the rows routing asked of the held
experts: `moe_rows_computed` / `moe_pairs` over the traced stretch's
steps (program_counter).  1.0 = only what routing asked; experts held
(8.0 here) = every held expert over every row, the dense product."""
from benchmarks.moe_train_counts import counts


def read(ctx, metric):
    got = counts(ctx)
    if got is None or not got["pairs"]:
        return None
    ctx.out(f"moe_train.rows_computed_over_routed: {got['rows_computed']} "
            f"rows multiplied for {got['pairs']} routed pairs over "
            f"{got['steps']} steps")
    return got["rows_computed"] / got["pairs"]
