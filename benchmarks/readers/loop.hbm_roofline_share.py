"""`loop.hbm_roofline_share.capacity`: a looped family's decode pass
against its byte floor, %: the least time the chip's memory could take
to move what the pass cannot avoid (the family's `decode_pass_bytes`:
the repeated region's weights once a loop step, the head once, the rows'
lines of the table, the live pages of every plane) at the published
bandwidth, over the device busy time of a decode dispatch
(`decode.device_ms`) (device_trace).  `decode.hbm_roofline_share`'s
division for a family whose `decode_pass_bytes` takes the loop steps
and no expert count.  None for any other family."""
import inspect

from benchmarks.reduce_trace import mean_dispatch_busy_ms
from benchmarks.decode_dispatch import dispatch_args


def read(ctx, metric):
    fam = ctx.family
    if (not ctx.peak or not hasattr(fam, "decode_pass_bytes")
            or "loop_steps" not in inspect.signature(
                fam.decode_pass_bytes).parameters):
        return None
    busy_ms = mean_dispatch_busy_ms(ctx.trace_summary, "jit_step")
    got = dispatch_args(ctx, "loop_steps", "kv_blocks_live", "slots")
    if not busy_ms or got is None:
        return None
    least_s = fam.decode_pass_bytes(
        ctx.cfg, rows=got["slots"], loop_steps=got["loop_steps"],
        kv_blocks_live=got["kv_blocks_live"]) / ctx.peak["hbm_bytes_per_s"]
    ctx.out(f"loop.hbm_roofline_share: least {1e3 * least_s:.3f} ms a pass "
            f"({got['loop_steps']:.1f} weight passes, "
            f"{got['kv_blocks_live']:.0f} live blocks) against "
            f"{busy_ms:.3f} ms busy")
    return 100.0 * 1e3 * least_s / busy_ms
