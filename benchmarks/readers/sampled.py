"""`sampled.*.capacity`: what a serving dispatch counts, whichever
program ran it (ISSUE 53): the args of the stretch's SAMPLING
dispatches, `sched.decode.dispatch` spans and `sched.prefill.dispatch`
spans that carry `decode_rows` (`sampling_dispatch.py`), from the span
ring (program_counter).  Each has a twin under `readers/` that reads
the decode program alone and falls silent where the pass is the whole
iteration; one file serves the family (`run.py load_module` falls back
to the stem):

* `sampled.rows`: rows that took a sampled token, a sampling dispatch
  (`rows` of a decode dispatch, `decode_rows` of a pass);
* `sampled.kv_read_share`: `kv_blocks_live` over `kv_blocks_dense`,
  summed, % (`kv.read_share`);
* `sampled.moe_held_pairs`: `moe_pairs` a sampling dispatch, summed
  over the routed layers; a pass counts its REAL tokens alone, a decode
  dispatch every slot's row; `moe_dropped` (which has to read 0) and
  `moe_hit` on the earlier line (`moe.held_pairs`);
* `sampled.moe_load_max_over_mean`: `moe_max_rows` x experts held over
  `moe_pairs` (`moe.load_max_over_mean`);
* `sampled.moe_zero_pick_share`: `moe_zero_picks` over the picks made,
  %: the real `tokens` x `moe_topk` x routed layers of a pass, `slots` x
  ... of a decode dispatch (`moe.zero_pick_share`);
* `sampled.loop_weight_passes`: `loop_steps` a sampling dispatch
  (`loop.weight_passes`);
* `sampled.exit_expected_pass`: 1 + sum t x `exit_mass_<t>`
  (`loop.exit_expected_pass`);
* `sampled.eva_live_over_read`: (`eva_rows_window` +
  `eva_rows_summary`) over `eva_rows_read`, summed, %
  (`eva.live_over_read`).

None where no sampling dispatch of the stretch carries the args (a
family without such layers; a tree whose pass fetches no counts).
"""
from benchmarks import sampling_dispatch as sd
from benchmarks.sampling_dispatch import dispatch_args


def rows(ctx):
    spans = sd.dispatches(ctx)
    if not spans:
        return None
    took = [r.args["rows"] if r.name == "sched.decode.dispatch"
            else r.args["decode_rows"] for r in spans]
    riding = [r.args.get("rows", 0) - r.args["decode_rows"] for r in spans
              if r.name == "sched.prefill.dispatch"]
    ctx.out(f"sampled.rows: {sum(took) / len(spans):.3f} rows took a token "
            f"a sampling dispatch over {sd.by_program(spans)}"
            + (f"; {sum(riding) / len(riding):.3f} more rows a pass still "
               "feeding their prompt" if riding else ""))
    return sum(took) / len(spans)


def kv_read_share(ctx):
    got = dispatch_args(ctx, "kv_blocks_live", "kv_blocks_dense")
    if got is None or not got["kv_blocks_dense"][0]:
        return None
    return 100.0 * got["kv_blocks_live"][0] / got["kv_blocks_dense"][0]


def moe_held_pairs(ctx):
    got = dispatch_args(ctx, "moe_pairs", "moe_dropped", "moe_hit")
    if got is None:
        return None
    ctx.out(f"sampled.moe_held_pairs: {got['moe_pairs'][1]:.2f} pairs on "
            f"held experts, {got['moe_dropped'][0]:.3g} dropped in all, "
            f"{got['moe_hit'][1]:.2f} held experts hit, a sampling dispatch "
            f"over {got['n']} (summed over the routed layers; a pass counts "
            "its real tokens alone)")
    return got["moe_pairs"][1]


def moe_load_max_over_mean(ctx):
    got = dispatch_args(ctx, "moe_pairs", "moe_max_rows")
    if got is None or not got["moe_pairs"][0]:
        return None
    return got["moe_max_rows"][0] * ctx.cfg["n_routed_experts"] \
        / got["moe_pairs"][0]


def moe_zero_pick_share(ctx):
    top_k, layers = ctx.cfg.get("moe_topk"), ctx.cfg.get("num_layers")
    spans = [r for r in sd.dispatches(ctx) or ()
             if "moe_zero_picks" in r.args and "slots" in r.args]
    if not spans or not top_k or not layers:
        return None
    # a decode step computes every slot's row; a pass counts its real
    # tokens alone
    picks = sum(r.args["slots"] if r.name == "sched.decode.dispatch"
                else r.args["tokens"] for r in spans) * top_k * layers
    zero = sum(r.args["moe_zero_picks"] for r in spans)
    if not picks:
        return None
    ctx.out(f"sampled.moe_zero_pick_share: {zero / len(spans):.1f} identity "
            f"picks of {picks / len(spans):.0f} a sampling dispatch over "
            f"{sd.by_program(spans)}; real picks a row: least "
            f"{min(r.args['moe_real_min'] for r in spans)}, mean "
            f"{top_k * (1 - zero / picks):.2f}, most "
            f"{max(r.args['moe_real_max'] for r in spans)} of {top_k}")
    return 100.0 * zero / picks


def loop_weight_passes(ctx):
    got = dispatch_args(ctx, "loop_steps")
    return None if got is None else got["loop_steps"][1]


def exit_expected_pass(ctx):
    spans = [r for r in sd.dispatches(ctx) or () if "exit_mass_0" in r.args]
    if not spans:
        return None
    passes = 0
    while f"exit_mass_{passes}" in spans[0].args:
        passes += 1
    mass = [sum(r.args[f"exit_mass_{t}"] for r in spans) / len(spans)
            for t in range(passes)]
    ctx.out("sampled.exit_expected_pass: exit pdf by pass "
            + " ".join(f"{m:.4f}" for m in mass)
            + f", mean over {sd.by_program(spans)}")
    return 1.0 + sum(t * m for t, m in enumerate(mass))


def eva_live_over_read(ctx):
    got = dispatch_args(ctx, "eva_rows_window", "eva_rows_summary",
                        "eva_rows_read")
    if got is None or not got["eva_rows_read"][0]:
        return None
    window, summary = got["eva_rows_window"][0], got["eva_rows_summary"][0]
    ctx.out(f"sampled.eva_live_over_read: {window + summary} live rows "
            f"({window} of windows, {summary} summaries) of "
            f"{got['eva_rows_read'][0]} read over {got['n']} sampling "
            "dispatches")
    return 100.0 * (window + summary) / got["eva_rows_read"][0]


READ = {f.__name__: f for f in (
    rows, kv_read_share, moe_held_pairs, moe_load_max_over_mean,
    moe_zero_pick_share, loop_weight_passes, exit_expected_pass,
    eva_live_over_read)}


def read(ctx, metric):
    return READ[metric["name"].split(".")[1]](ctx)
