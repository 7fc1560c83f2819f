"""`prefill.roofline_share.capacity`: the least time the chip could take
for what one chunked-prefill pass was asked to do, over the device busy
time of a prefill dispatch (`prefill.device_ms`), % (device_trace).  The
least time is the larger of the family's `prefill_pass_flops` over the
peak's bf16 rate and its `prefill_pass_bytes` over its bandwidth
(`peaks.json`), both counted from the configuration and the dispatch's
own counts (`tokens` and `kv_blocks_live` of `sched.prefill.dispatch`,
means over the traced stretch): the pairs routing asked for, never the
rows a dense product ran, and no identity pick, so the share cannot pass
100.  None for a family without the two functions."""
from benchmarks import host_spans as hs
from benchmarks.reduce_trace import mean_dispatch_busy_ms


def read(ctx, metric):
    fam = ctx.family
    if not hasattr(fam, "prefill_pass_flops") or not ctx.peak:
        return None
    busy_ms = mean_dispatch_busy_ms(ctx.trace_summary, "jit_prefill")
    found = hs.ring(ctx)
    if not busy_ms or found is None:
        return None
    spans = [r for r in hs.named(found[0], "sched.prefill.dispatch")
             if r.args.get("tokens") and "kv_blocks_live" in r.args]
    if not spans:
        return None
    mean = {k: sum(r.args[k] for r in spans) / len(spans)
            for k in ("tokens", "rows", "kv_blocks_live")}
    tokens = mean["tokens"]
    # positions a row's live blocks hold, less the last block's slack
    page = ctx.cfg["deployment"]["kv_page_size"]
    context = max(0.0, page * (mean["kv_blocks_live"]
                               / max(1.0, mean["rows"]) - 1))
    by_flops = fam.prefill_pass_flops(ctx.cfg, tokens, context) \
        / ctx.peak["bf16_flops_per_s"]
    by_bytes = fam.prefill_pass_bytes(ctx.cfg, tokens) \
        / ctx.peak["hbm_bytes_per_s"]
    ctx.out(f"prefill.roofline_share: least {1e3 * by_flops:.3f} ms by "
            f"operations, {1e3 * by_bytes:.3f} ms by bytes a pass "
            f"({tokens:.1f} tokens, context {context:.0f}) against "
            f"{busy_ms:.3f} ms busy")
    return 100.0 * 1e3 * max(by_flops, by_bytes) / busy_ms
