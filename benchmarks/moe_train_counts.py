"""Sums of the args the trainer puts on `train_step.moe`, over the
traced stretch (from the span ring: program_counter).  A `train_step`
reports, without waiting, the routed-expert counts of the newest
earlier step whose counts had arrived; each span is one step's counts,
summed over the routed layers.  Shared by the `moe_train.*` readers."""
from benchmarks import host_spans as hs


def counts(ctx):
    """{"steps", "pairs", "dropped", "max_rows", "hit",
    "rows_computed"}: sums over the stretch's `train_step.moe` spans, or
    None where the program emits none (no routed layer, or the parent
    of PR 36)."""
    found = hs.ring(ctx)
    if found is None:
        return None
    spans = hs.named(found[0], "train_step.moe")
    if not spans:
        return None
    return {
        "steps": len(spans),
        "pairs": sum(r.args["moe_pairs"] for r in spans),
        "dropped": sum(r.args["moe_dropped"] for r in spans),
        "max_rows": sum(r.args["moe_max_rows"] for r in spans),
        "hit": sum(r.args["moe_hit"] for r in spans),
        "rows_computed": sum(r.args["moe_rows_computed"] for r in spans),
    }
