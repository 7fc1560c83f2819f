"""`decode.hbm_roofline_share.capacity`: the least time the chip's
memory could take to read what one decode pass cannot avoid reading
(the family's `decode_pass_bytes`: every weight outside the routed
experts, the held experts that received a row, the live pages of the
latent pool) at the published bandwidth, over the device busy time of a
decode dispatch (`decode.device_ms`), % (device_trace).  A decode pass
of a few rows is bound by bytes, not by operations; no kernel is new in
PR 29, so this is the step's share, not a kernel's."""
from benchmarks.reduce_trace import mean_dispatch_busy_ms
from benchmarks.decode_dispatch import dispatch_args


def read(ctx, metric):
    fam = ctx.family
    if not hasattr(fam, "decode_pass_bytes") or not ctx.peak:
        return None
    busy_ms = mean_dispatch_busy_ms(ctx.trace_summary, "jit_step")
    got = dispatch_args(
        ctx, "moe_hit", "kv_blocks_live", "slots")
    if not busy_ms or got is None:
        return None
    least_s = fam.decode_pass_bytes(
        ctx.cfg, rows=got["slots"], experts_hit=got["moe_hit"],
        kv_blocks_live=got["kv_blocks_live"],
        kv_block_bytes=fam.latent_block_bytes(ctx.cfg),
    ) / ctx.peak["hbm_bytes_per_s"]
    ctx.out(f"decode.hbm_roofline_share: least {1e3 * least_s:.3f} ms of "
            f"reads a pass ({got['moe_hit']:.1f} held experts hit, "
            f"{got['kv_blocks_live']:.0f} live blocks) against "
            f"{busy_ms:.3f} ms busy")
    return 100.0 * 1e3 * least_s / busy_ms
