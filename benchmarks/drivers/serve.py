"""Open-loop serving window on `ServingFront.generate_async`, measured
in steady state.

Traffic file (all lengths in tokens)::

    {"driver": "serve", "rate_rps": r,
     "warm_burst": 16, "warm_completions": 16, "warm_seconds": 45,
     "prompt_len": {"median": 128, "sigma": 0.8, "min": 16, "max": 768},
     "new_tokens": {"median": 48, "sigma": 0.7, "min": 8, "max": 192},
     "max_total": 1024, "shape_seed": 1, "drain_seconds": 120,
     "check_requests": 40, "check_min_requests": 32,
     "check_min_positions": 1500, "trace_seconds": 4}

The generator is general: a later cell is a new data file.  The same
mix is offered BEFORE the window opens (starting with ``warm_burst``
requests at once, which fill the slots), until ``warm_completions``
requests have completed (``warm_seconds`` at the latest), so that the
window at t0 finds the server as a replica under that load is, not
empty; that stretch is set-up.  Request sizes and arrival instants are
a fixed trace drawn from ``shape_seed`` in the file and replayed under
every ``--seed``; the seed draws the token ids (and the weights).  So
every seed offers the same work at the same instants: a replica that
completes three requests in four seconds (PR 25, chip) finishes some
twenty in a 30 s window, and twenty other requests each time would be
another number each time.

Open loop: a request is sent when it is due whatever the server is
doing, and every latency runs from the instant it was DUE on the
monotonic clock the front's handles are stamped with
(`serving/loadgen.py` ran its clock from when the generator got round
to sending).  How late the generator itself ran is reported
(`loadgen.late_p95_ms`).  Tails are taken over the requests due inside
the window.

``correct`` is teacher-forced (families' `position_regrets`): for a
seeded sample of the run's completed requests the plain float32
reference runs one full forward over each returned sequence and scores
every served token by its regret; beside it exact checks (prompt
echoed, length = prompt + asked, ids in range).  Outside the timed
window.
"""
from __future__ import annotations

import json
import threading
import time

import jax.numpy as jnp
import numpy as np


# -- traffic ------------------------------------------------------------------
def _lognormal(rng, spec, n):
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def make_schedule(tr, seed: int, seconds: float, vocab: int, rate=None):
    """[(due_s, prompt ids, new tokens)] sorted by due time, counted
    from the run's start: ``warm_burst`` requests at 0, then Poisson
    arrivals at ``rate``, far enough for the latest window
    (``warm_seconds`` + ``seconds``).  The trace is one endless
    sequence cut off there: a longer window only adds requests behind
    the same ones."""
    rate = tr["rate_rps"] if rate is None else rate
    burst, horizon = tr["warm_burst"], tr["warm_seconds"] + seconds
    most = burst + int(2 * rate * horizon) + 16
    # a stream of its own for each quantity, so that the first n of
    # each are the same whatever the horizon
    plens, news, gaps = (np.random.default_rng([tr["shape_seed"], k])
                         for k in range(3))
    plen = _lognormal(plens, tr["prompt_len"], most)
    new = np.minimum(_lognormal(news, tr["new_tokens"], most),
                     tr["max_total"] - plen)
    due = np.concatenate([np.zeros(burst), np.cumsum(
        gaps.exponential(1.0 / rate, most - burst))])
    if due[-1] < horizon:
        raise ValueError("the trace ran out before the window's end")
    ids = np.random.default_rng(seed)               # token ids only
    return [(float(due[i]), ids.integers(1, vocab, int(plen[i])).tolist(),
             int(new[i])) for i in range(int(np.searchsorted(due, horizon)))]


# -- one window ---------------------------------------------------------------
def serve_window(ctx, front, schedule, seconds: float, drain_s: float):
    """Send every request when it is due until the window's end, read
    the front's counters at the window's two ends, wait for the
    stragglers, and return (records, t0, {"t0": stats, "t_end": stats}).

    The window opens (t0) the instant the ``warm_completions``-th
    request is seen to complete, ``warm_seconds`` after the start at
    the latest, and lasts ``seconds``.  An opening tied to the server's
    own progress keeps the window on the same stretch of the replayed
    trace when a run's start is a little slower or faster; a fixed
    instant would let a completion near an end fall on either side.

    With ``--trace 1`` the profiler is opened a third of the way into
    the window, for ``trace_seconds``, from a thread of its own:
    starting and stopping it takes seconds, which the sender must not
    spend."""
    tr = ctx.traffic
    tracer = None

    def trace_a_stretch():
        time.sleep(seconds / 3)
        ctx.trace_start()
        time.sleep(tr["trace_seconds"])
        ctx.trace_stop()

    def read_counters():
        return dict(front.stats(), lowered=ctx.watch.snapshot()["lowered"])

    start = time.monotonic()
    t0, ends, sent, nxt = None, {}, [], 0
    while True:
        now = time.monotonic()
        if t0 is None and (
                now - start >= tr["warm_seconds"]
                or sum(1 for r in sent if r["handle"] is not None
                       and r["handle"].t_done is not None)
                >= tr["warm_completions"]):
            t0, ends["t0"] = now, read_counters()
            if ctx.trace:
                tracer = threading.Thread(target=trace_a_stretch, daemon=True)
                tracer.start()
        if t0 is not None and now >= t0 + seconds:
            ends["t_end"] = read_counters()
            break
        if nxt < len(schedule) and now >= start + schedule[nxt][0]:
            due, prompt, asked = schedule[nxt]
            nxt += 1
            rec = {"due": start + due, "prompt": prompt, "asked": asked,
                   "sent": time.monotonic(), "handle": None, "error": None}
            try:
                rec["handle"] = front.generate_async(prompt, asked, 0.0)
            except Exception as e:  # refused at admission: a failed request
                rec["error"] = f"{type(e).__name__}: {e}"
            sent.append(rec)
            continue
        wake = t0 + seconds if t0 is not None else now + 0.005
        if nxt < len(schedule):
            wake = min(wake, start + schedule[nxt][0])
        time.sleep(max(0.0, min(0.005, wake - time.monotonic())))
    deadline = time.monotonic() + drain_s
    for rec in sent:
        h = rec["handle"]
        if h is None:
            continue
        try:
            rec["tokens"] = h.wait(max(0.0, deadline - time.monotonic()))
        except Exception as e:  # timed out or failed inside the server
            rec["error"] = f"{type(e).__name__}: {e}"
    if tracer is not None:
        tracer.join()
    return sent, t0, ends


def p95(values):
    return float(np.percentile(np.asarray(values, np.float64), 95))


def summarize(sent, t0, seconds, vocab):
    """``serve_tokens_per_s``: the output tokens of every request that
    was seen to complete inside the window, over its seconds: nothing
    is estimated for a request that straddles either end (in steady
    state what one end cuts off the other brings in).  Tails run from
    each request's DUE instant, over the requests due inside the
    window.  A request of the run that errored, did not finish in the
    drain, or came back with a wrong length, prompt or id counts in
    ``failed``."""
    ttft, gap, late, done_tokens, done_n, wrong, failed = [], [], [], 0, 0, 0, 0
    t_end, edge = t0 + seconds, float("inf")
    for rec in sent:
        h = rec["handle"]
        late.append(rec["sent"] - rec["due"])
        if rec["error"] is not None or h is None or h.t_first_token is None:
            failed += 1
            continue
        toks, plen = rec["tokens"], len(rec["prompt"])
        if (len(toks) != plen + rec["asked"] or toks[:plen] != rec["prompt"]
                or min(toks) < 0 or max(toks) >= vocab):
            wrong += 1
            failed += 1
            continue
        # (the window opens AT a completion, which is not near it)
        edge = min(edge, abs(h.t_done - t_end),
                   h.t_done - t0 if h.t_done > t0 else edge)
        if t0 < h.t_done <= t_end:
            done_tokens += h.n_generated
            done_n += 1
        if rec["due"] >= t0:
            ttft.append(h.t_first_token - rec["due"])
            if h.n_generated > 1:
                gap.append((h.t_done - h.t_first_token) / (h.n_generated - 1))
    out = {"attempted": len(sent), "failed": failed, "wrong": wrong,
           "due_in_window": sum(1 for r in sent if r["due"] >= t0),
           "completed_in_window": done_n,
           "serve_tokens_per_s": done_tokens / seconds,
           "completion_nearest_an_end_s": edge,
           "loadgen.late_p95_ms": 1e3 * p95(late)}
    if ttft:
        out["ttft_p95_ms"] = 1e3 * p95(ttft)
        out["ttft_p50_ms"] = 1e3 * float(np.median(ttft))
    if gap:
        out["gap_p95_ms"] = 1e3 * p95(gap)
        out["gap_p50_ms"] = 1e3 * float(np.median(gap))
    return out


# -- correct ------------------------------------------------------------------
def check_served(ctx, sent, seed: int, wrong: int, controls=()) -> dict:
    """{"program": {regret.mean, exact.wrong_outputs}}: teacher-forced
    regret of the served tokens of a seeded sample of completed
    requests.  Each precision in ``controls`` adds the regret of what
    the reference at that precision would have served in the same
    contexts (`sweep`, the tests and ``--controls``; never a run of the
    driver's check)."""
    fam, cfg, tr = ctx.family, ctx.cfg, ctx.traffic
    good = [r for r in sent if r["error"] is None and r.get("tokens")]
    rng = np.random.default_rng(seed)
    pick = rng.permutation(len(good))[:tr["check_requests"]]
    w = fam.make_weights(cfg, seed, "reference")
    width = cfg["n_positions"]
    choosers = {"program": None, **{p: p for p in controls}}
    total, count = dict.fromkeys(choosers, 0.0), 0
    for i in pick:
        rec = good[int(i)]
        toks, plen = rec["tokens"], len(rec["prompt"])
        ids = np.zeros(width, np.int32)
        ids[:len(toks)] = toks
        count += len(toks) - plen
        for name, chooser in choosers.items():
            regret = np.asarray(fam.position_regrets(w, jnp.asarray(ids),
                                                     chooser=chooser))
            # logits at p judge the token at p + 1
            total[name] += float(regret[plen - 1:len(toks) - 1].sum())
    ctx.out(f"check: {len(pick)} requests, {count} served positions")
    out = {}
    for name in choosers:
        out[name] = {"exact.wrong_outputs": float(wrong)}
        # too small a sample gives no statistic, and so `correct: false`
        if (len(pick) >= tr["check_min_requests"]
                and count >= tr["check_min_positions"]):
            out[name]["regret.mean"] = total[name] / count
    return out


# -- bring-up -----------------------------------------------------------------
def open_front(ctx, ff, seed: int):
    """The seed's weights into the program, a front over them, and one
    request through every program the window will dispatch (chunked
    prefill, the one-token step for a prompt's remainder, decode)."""
    from flexflow_tpu.serving import build_front

    fam, cfg = ctx.family, ctx.cfg
    with ctx.span("make_weights"):
        ff.set_weights(fam.make_weights(cfg, seed, "program"))
    with ctx.span("build_front"):
        front = build_front(ff)
    with ctx.span("first_requests"):
        rng = np.random.default_rng(seed)
        warm = [front.generate_async(
            rng.integers(1, cfg["vocab_size"], n).tolist(), 4, 0.0)
            for n in (19, 40)]
        for h in warm:
            h.wait(600.0)
    return front


def one_window(ctx, front, seed: int, seconds: float, rate=None):
    """The warm stretch and one window at ``rate`` (the traffic file's
    by default): (summary, records, t0)."""
    cfg, tr = ctx.cfg, ctx.traffic
    schedule = make_schedule(tr, seed, seconds, cfg["vocab_size"], rate)
    sent, t0, ends = serve_window(ctx, front, schedule, seconds,
                                  tr["drain_seconds"])
    s = summarize(sent, t0, seconds, cfg["vocab_size"])
    a, b = ends["t0"], ends["t_end"]
    # the program's own counter beside the benchmark's count
    s["sched.tokens_per_s"] = (b["tokens_generated"]
                               - a["tokens_generated"]) / seconds
    s["queue_depth_at_t0"] = a["queue_depth"]
    s["queue_depth_at_end"] = b["queue_depth"]
    s["compiles_in_window"] = b["lowered"] - a["lowered"]
    ctx.out("window " + " ".join(f"{k}={v:.6g}" for k, v in s.items()))
    # when each request was done, against the window's opening, and
    # its output tokens: shows how near a completion falls to an end
    ctx.out("completions " + " ".join(
        f"{r['handle'].t_done - t0:.2f}:{r['handle'].n_generated}"
        for r in sorted((r for r in sent if r["handle"] is not None
                         and r["handle"].t_done is not None),
                        key=lambda r: r["handle"].t_done)))
    return s, sent, t0


def control_names(ctx) -> dict:
    from benchmarks.reference import control_precisions

    return control_precisions(ctx.cfg["precision"])


def sweep(ctx, seeds, control_seeds):
    """Rule 3's readings in one process: per seed a window and the
    regret of its served tokens, and for ``control_seeds`` the regret
    of what the reference one precision down (and at the stated
    precision, for scale) would have served in the same contexts."""
    with ctx.span("ffmodel_compile"):
        ff = ctx.family.build_server(ctx.cfg, ctx.devices)
    names = control_names(ctx)
    for seed in seeds:
        front = open_front(ctx, ff, seed)
        try:
            s, sent, _ = one_window(ctx, front, seed, ctx.seconds)
        finally:
            front.close(30.0)
        stats = check_served(
            ctx, sent, seed, s["wrong"],
            controls=tuple(names) if seed in control_seeds else ())
        yield {"seed": seed, "failed": s["failed"],
               **{names.get(k, k): v for k, v in stats.items()}}


def run(ctx) -> dict:
    with ctx.span("ffmodel_compile"):
        ff = ctx.family.build_server(ctx.cfg, ctx.devices)
    front = open_front(ctx, ff, ctx.seed)
    names = control_names(ctx) if ctx.args.controls else {}
    try:
        s, sent, t0 = one_window(ctx, front, ctx.seed, ctx.seconds)
        ctx.counters.update(s)
        pool = kv_pool_stats(front)
        if pool:
            ctx.out("kv_pool " + json.dumps(pool))
        try:
            with ctx.span("check"):
                checked = check_served(ctx, sent, ctx.seed, s["wrong"],
                                       controls=tuple(names))
        except Exception as e:  # rule 4: a check that cannot be made fails
            ctx.out(f"check could not be made: {type(e).__name__}: {e}")
            checked = {"program": {}}
    finally:
        front.close(30.0)
    for precision, name in names.items():  # builder's sweeps; decides nothing
        ctx.out(f"{name} ({precision}) " + json.dumps(checked[precision]))
    # the warm stretch is set-up: the window opens at t0
    s["setup_s"] = t0 - ctx.t_process_start
    return {"end_to_end": s, "attempted": s["attempted"],
            "failed": s["failed"], "stats": checked["program"],
            "compiles_in_window": s["compiles_in_window"]}


def kv_pool_stats(front):
    """The scheduler's pool counters for an earlier line (PERF.md's
    share of the chip holding live KV).  `front.stats()` does not carry
    them, so this reaches past the public handle; it feeds no metric."""
    try:
        return front.replicas[0].scheduler.stats()["kv_pool"]
    except Exception:
        return None
