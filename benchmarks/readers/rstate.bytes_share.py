"""`rstate.bytes_share.capacity`: the live rows' recurrent state, read
and written, as a share of what a decode pass cannot avoid moving (the
family's `decode_pass_bytes`: every weight outside the routed experts,
the held experts hit, the live k/v pages, 2 x the live rows' state), %:
means over the traced stretch's decode dispatches of the
`rstate_rows_live`, `moe_hit`, `kv_blocks_live` and `slots` args of
`sched.decode.dispatch` (program_counter).  How much of the pass's
unavoidable traffic is the state this family adds.  None where the
spans carry no such args or the family counts no such bytes."""
from benchmarks.decode_dispatch import dispatch_args


def read(ctx, metric):
    fam = ctx.family
    if not hasattr(fam, "rstate_row_bytes"):
        return None
    got = dispatch_args(ctx, "rstate_rows_live", "moe_hit",
                        "kv_blocks_live", "slots")
    if got is None:
        return None
    state = 2.0 * got["rstate_rows_live"] * fam.rstate_row_bytes(ctx.cfg)
    whole = fam.decode_pass_bytes(
        ctx.cfg, rows=got["slots"], experts_hit=got["moe_hit"],
        kv_blocks_live=got["kv_blocks_live"],
        kv_block_bytes=fam.latent_block_bytes(ctx.cfg),
        rstate_rows_live=got["rstate_rows_live"])
    ctx.out(f"rstate.bytes_share: {state / 1e6:.1f} MB of state read and "
            f"written for {got['rstate_rows_live']:.2f} live rows of "
            f"{got['slots']:.0f} slots, in {whole / 1e6:.1f} MB a decode "
            "pass cannot avoid")
    return 100.0 * state / whole
