"""`indexer.roofline_share.capacity`: the indexer's scores and picks'
share of their roofline, %: the least time the chip could take for a
dispatch's index scores, the larger of the family's
`index_scores_flops` of the dispatches' mean `dsa_keys_scored` over the
bf16 peak and its `index_read_bytes` of their mean `index_blocks_live`
over the published bandwidth, over the device time a dispatch spends
under the scopes `MLAttention | index_scores` and `| topk`, in EITHER
step program (device_trace).  The exact top-k has no operations in the
count: what it costs lowers the share.  None for a family without the
counts and where the stretch ran no such scope."""
from benchmarks import device_scopes as ds
from benchmarks import host_spans as hs

PROGRAMS = {"sched.decode.dispatch": "step",
            "sched.prefill.dispatch": "prefill"}
ARGS = ("dsa_keys_scored", "index_blocks_live")
PARTS = ("index_scores", "topk")


def read(ctx, metric):
    fam = ctx.family
    if not hasattr(fam, "index_scores_flops"):
        return None
    view, found = ds.scope_view(ctx), hs.ring(ctx)
    if view is None or found is None or not ctx.peak:
        return None
    rows, per = view
    spans = [r for r in found[0] if r.name in PROGRAMS
             and all(a in r.args for a in ARGS)]
    programs = tuple({PROGRAMS[r.name] for r in spans})
    mine = ds.total(rows, program=programs, kind="MLAttention", part=PARTS)
    runs = sum(per.get(p, 0) for p in programs)
    if not spans or not mine.seconds or not runs:
        return None
    seconds = mine.seconds
    scored, blocks = (sum(r.args[a] for r in spans) / len(spans)
                      for a in ARGS)
    by_flops = fam.index_scores_flops(ctx.cfg, scored) \
        / ctx.peak["bf16_flops_per_s"]
    by_bytes = fam.index_read_bytes(ctx.cfg, blocks) \
        / ctx.peak["hbm_bytes_per_s"]
    took_ms = 1e3 * seconds / runs
    ctx.out(f"indexer.roofline_share: least {1e3 * by_flops:.3f} ms by "
            f"operations ({scored:.0f} scores a dispatch), "
            f"{1e3 * by_bytes:.3f} ms by bytes ({blocks:.0f} live blocks of "
            f"index keys) against {took_ms:.3f} ms a dispatch under "
            f"index_scores + topk ({runs} runs)")
    return 100.0 * 1e3 * max(by_flops, by_bytes) / took_ms
