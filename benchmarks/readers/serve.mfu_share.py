"""`serve.mfu_share.capacity`: the whole serving step's share of the
chip's peak, %: the least time the chip could take for what the traced
stretch's dispatches of EITHER step program were asked to do, over the
stretch's device busy time (device_trace).  A decode pass is bound by
bytes (what it cannot avoid reading over the published bandwidth), a
prefill pass by the larger of its operations over the bf16 peak and its
bytes over the bandwidth; both counted by the family from the
configuration and each dispatch's own span args (`sched.decode.dispatch`,
`sched.prefill.dispatch`: rows, tokens and what the layers' state held
for them), never from padded shapes, so the share cannot pass 100.  The
mean least time of a program's dispatches in the stretch is taken as
often as the device ran that program in it.

The family's counts are asked for in this order: `dispatch_least_s(cfg,
peak, program, args)` (families/evabyte.py); else `decode_pass_bytes`
with `latent_block_bytes` as `decode.hbm_roofline_share` calls them and
`prefill_pass_flops` / `prefill_pass_bytes` as `prefill.roofline_share`
does.  None for a family without counts for a program the stretch ran,
and only then or when the stretch dispatched nothing."""
from benchmarks import host_spans as hs

PROGRAMS = {"decode": ("sched.decode.dispatch", "jit_step"),
            "prefill": ("sched.prefill.dispatch", "jit_prefill")}


def least_s(ctx, program: str, args: dict):
    fam, cfg, peak = ctx.family, ctx.cfg, ctx.peak
    if hasattr(fam, "dispatch_least_s"):
        return fam.dispatch_least_s(cfg, peak, program, args)
    if program == "decode":
        if not (hasattr(fam, "decode_pass_bytes")
                and hasattr(fam, "latent_block_bytes")
                and {"moe_hit", "kv_blocks_live", "slots"} <= set(args)):
            return None
        return fam.decode_pass_bytes(
            cfg, rows=args["slots"], experts_hit=args["moe_hit"],
            kv_blocks_live=args["kv_blocks_live"],
            kv_block_bytes=fam.latent_block_bytes(cfg),
        ) / peak["hbm_bytes_per_s"]
    if not (hasattr(fam, "prefill_pass_flops") and args.get("tokens")
            and "kv_blocks_live" in args):
        return None
    page = cfg["deployment"]["kv_page_size"]
    context = max(0.0, page * (args["kv_blocks_live"]
                               / max(1.0, args["rows"]) - 1))
    return max(fam.prefill_pass_flops(cfg, args["tokens"], context)
               / peak["bf16_flops_per_s"],
               fam.prefill_pass_bytes(cfg, args["tokens"])
               / peak["hbm_bytes_per_s"])


def read(ctx, metric):
    found = hs.ring(ctx)
    summary = ctx.trace_summary
    if found is None or not summary or not ctx.peak \
            or not summary.get("busy_s"):
        return None
    total, said = 0.0, []
    for program, (span, module) in PROGRAMS.items():
        runs = len(summary["modules"].get(module, ()))
        spans = hs.named(found[0], span)
        if not runs or not spans:
            continue
        each = [least_s(ctx, program, r.args) for r in spans]
        if any(x is None for x in each):
            return None  # no counts for a program that ran
        mean = sum(each) / len(each)
        total += mean * runs
        said.append(f"{runs} {program} dispatches of least "
                    f"{1e3 * mean:.3f} ms")
    if not said:
        return None
    ctx.out(f"serve.mfu_share: {', '.join(said)} against "
            f"{1e3 * summary['busy_s']:.1f} ms busy")
    return 100.0 * total / summary["busy_s"]
