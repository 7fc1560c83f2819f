"""`turn.*.capacity`: the host's turn between two serving dispatches,
split where it is spent (ISSUE 53).  From the end of one step program
on the device to the start of the next, the device waits for

    tail     the program's end -> the end of the `model.fetch` that
             waited for it (the logits' way to the host)
    between  that fetch's end -> the start of the next `model.enqueue`
             (sampling, retiring, admitting, preparing: the scheduler's
             own work, by innermost span on the earlier line)
    lag      that enqueue's start -> the program's start on the device
             (the jitted call: flattening its arguments, copying the
             host arrays in, the launch)

and `turn.enqueue_ms` is how long the jitted call holds the host (it
returns once the program is enqueued, so part of it runs beside the
device).  One file serves the four metrics (`run.py load_module` falls
back to the stem):

* `turn.enqueue_ms.capacity` (program_span): mean duration of the
  stretch's `model.enqueue` spans of the step programs (`program` in
  step / prefill / verify, lazy compiles left out), ms; by program on
  the earlier line with `arg_leaves` and `host_bytes`, and the same
  outside the stretch (profiler closed);
* `turn.launch_lag_ms.capacity` (device_trace): mean over the runs that
  were launched onto an idle device (the call before their enqueue was
  a fetch); a run enqueued behind a program nobody fetched (GPT's
  decode step behind its scanned prefill) waits for that program, not
  for the host, and is listed beside;
* `turn.fetch_tail_ms.capacity` (device_trace): mean over the fetched
  runs of either program; by program on the earlier line;
* `turn.between_ms.capacity` (program_span): mean over the stretch's
  fetched dispatches, from the ring; on earlier lines its split by
  innermost span, the same outside the stretch, and the closure: lag +
  tail + between, a fetched dispatch, against
  `sched.dispatch_ms.capacity` (the device's idle time a dispatch of
  ANY program, so the sum is scaled by the fetched share of the runs),
  with the residual.

The two device metrics stand on `sampling_dispatch.turn_view`: one
clock under both causal bounds, good to about a millisecond in the
split of lag against tail, to a fraction of one in their sum.  None on
a tree whose `model.enqueue` / `model.fetch` do not say their `program`
(the parent of PR 53).
"""
from benchmarks import host_spans as hs
from benchmarks import sampling_dispatch as sd
from benchmarks.run import load_module


def enqueue_ms(ctx):
    found = hs.ring(ctx)
    calls = sd.model_calls(found[0]) if found else None
    if calls is None:
        return None
    inside = [r for r in calls if r.name == "model.enqueue"]
    if not inside:
        return None
    outside = [r for r in sd.model_calls(found[1]) or ()
               if r.name == "model.enqueue"]
    for program in sd.PROGRAMS:
        mine = [r for r in inside if r.args["program"] == program]
        if mine:
            ctx.out(f"turn.enqueue_ms: {program}: "
                    f"{hs.fmt(hs.mean_ms(mine))} over {len(mine)} calls, "
                    f"{mine[0].args.get('arg_leaves')} argument leaves, "
                    f"{mine[0].args.get('host_bytes')} host bytes copied in; "
                    "outside the stretch " + hs.fmt(hs.mean_ms(
                        [r for r in outside
                         if r.args["program"] == program])))
    return hs.mean_ms(inside)


def launch_lag_ms(ctx):
    turns = sd.turn_view(ctx)
    if turns is None:
        return None
    lag = lambda t: t.run[0] - t.enqueue.start_s  # noqa: E731
    for program in sd.PROGRAMS:
        mine = [t for t in turns if t.program == program]
        idle = [lag(t) for t in mine if t.idle_launch]
        queued = [lag(t) for t in mine if not t.idle_launch]
        if mine:
            ctx.out(f"turn.launch_lag_ms: {program}: "
                    f"{hs.fmt(sd.mean_ms(idle))} over {len(idle)} runs "
                    "launched onto an idle device"
                    + (f" (least {1e3 * min(idle):.3f}, most "
                       f"{1e3 * max(idle):.3f})" if idle else "")
                    + (f"; {len(queued)} enqueued behind a running program "
                       f"start {hs.fmt(sd.mean_ms(queued))} after their "
                       "enqueue" if queued else ""))
    ctx.turn_lag_ms = sd.mean_ms(lag(t) for t in turns if t.idle_launch)
    return ctx.turn_lag_ms


def fetch_tail_ms(ctx):
    turns = sd.turn_view(ctx)
    if turns is None:
        return None
    tail = lambda t: t.fetch.end_s - t.run[1]  # noqa: E731
    fetched = [t for t in turns if t.fetch is not None]
    for program in sd.PROGRAMS:
        mine = [t for t in fetched if t.program == program]
        if mine:
            tails = [tail(t) for t in mine]
            ctx.out(f"turn.fetch_tail_ms: {program}: "
                    f"{hs.fmt(sd.mean_ms(tails))} over {len(mine)} fetched "
                    f"runs (least {1e3 * min(tails):.3f}, most "
                    f"{1e3 * max(tails):.3f}), "
                    f"{mine[0].fetch.stats.get('bytes')} bytes a fetch")
    ctx.turn_tail_ms = sd.mean_ms(map(tail, fetched))
    ctx.turn_fetched_share = len(fetched) / len(turns)
    return ctx.turn_tail_ms


def between_ms(ctx):
    found = hs.ring(ctx)
    turns = sd.betweens(found[0]) if found else None
    if not turns:
        return None
    value = sd.mean_ms(s for s, _ in turns)
    split = {}
    for _, by in turns:
        for name, s in by.items():
            split[name] = split.get(name, 0.0) + s
    ctx.out(f"turn.between_ms: {hs.fmt(value)} a fetched dispatch over "
            f"{len(turns)}, by innermost span: " + " ".join(
                f"{name}={1e3 * s / len(turns):.4f}" for name, s in sorted(
                    split.items(), key=lambda kv: -kv[1]))
            + "; outside the stretch (profiler closed) " + hs.fmt(
                sd.mean_ms(s for s, _ in sd.betweens(found[1]) or ())))
    lag = getattr(ctx, "turn_lag_ms", None)
    tail = getattr(ctx, "turn_tail_ms", None)
    idle = load_module("readers", "sched.dispatch_ms").read(ctx, {})
    if None not in (lag, tail, idle):
        share = ctx.turn_fetched_share
        total = (lag + tail + value) * share
        ctx.out(f"turn closure: lag {lag:.4f} + tail {tail:.4f} + between "
                f"{value:.4f} = {lag + tail + value:.4f} ms a fetched "
                f"dispatch, x {share:.3f} (the fetched share of the runs) = "
                f"{total:.4f} against sched.dispatch_ms {idle:.4f}: "
                f"residual {idle - total:+.4f} ms")
    return value


READ = {"enqueue_ms": enqueue_ms, "launch_lag_ms": launch_lag_ms,
        "fetch_tail_ms": fetch_tail_ms, "between_ms": between_ms}


def read(ctx, metric):
    return READ[metric["name"].split(".")[1]](ctx)
