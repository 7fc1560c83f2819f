"""`moe.zero_pick_share.capacity`: of the router's picks in the traced
stretch's decode dispatches (`slots` x `moe_topk` x the routed layers: a
decode step computes every slot's row), the share that fell on IDENTITY
experts, which multiply nothing, %: the `moe_zero_picks` arg of
`sched.decode.dispatch` (program_counter).  On an earlier line the
least, the mean and the most REAL picks a row made.  What a grouped or
hit-only expert product would not compute of what the dense product
does is this share and the held experts `moe_hit` leaves out.  None on
a tree, or for a family, without identity experts."""
from benchmarks import host_spans as hs

ARGS = ("moe_zero_picks", "moe_real_min", "moe_real_max", "slots")


def read(ctx, metric):
    found = hs.ring(ctx)
    top_k, layers = ctx.cfg.get("moe_topk"), ctx.cfg.get("num_layers")
    if found is None or not top_k or not layers:
        return None
    args = [r.args for r in hs.named(found[0], "sched.decode.dispatch")
            if all(n in r.args for n in ARGS)]
    picks = sum(a["slots"] for a in args) * top_k * layers
    if not picks:
        return None
    share = sum(a["moe_zero_picks"] for a in args) / picks
    ctx.out(f"moe.zero_pick_share: {share * picks / len(args):.1f} identity "
            f"picks of {picks / len(args):.0f} a decode dispatch; real picks "
            f"a row: least {min(a['moe_real_min'] for a in args)}, mean "
            f"{top_k * (1 - share):.2f}, most "
            f"{max(a['moe_real_max'] for a in args)} of {top_k}")
    return 100.0 * share
