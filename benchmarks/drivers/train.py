"""Timed training window on `FFModel.train_step`.

Traffic file: ``{"driver": "train", "batch_per_chip": b, "seq": s,
"distinct_batches": k, "in_flight": n, "trace_seconds": t}``.

Set-up (all counted in ``setup_s``): build the family's graph,
`FFModel.compile`, weights from the seed through `set_weights`, ONE
train step on the seed's first batch whose gradients are compared with
the plain reference (`check_first_step`: that decides ``correct``), a
few more steps so nothing compiles later.  Then the window: batches are
fed from the host every step as `fit()` does, the losses stay on the
device, and the host waits only for the step ``in_flight`` behind the
one it dispatches (so it runs that far ahead of the device, and no
further) and, once, for the last.  tokens/s/chip is every token of
every step of the window over the window's seconds over the chips.
"""
from __future__ import annotations

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import check


def first_step_grads(ff, cfg):
    """The program's first-step gradient, read from Adam's first moment:
    after one step from m = 0 with weight decay 0, m = (1 - beta1) * g
    exactly.  (`ff._opt_state` is what checkpoint.py saves.)"""
    scale = 1.0 / (1.0 - cfg["optimizer"]["beta1"])
    m = ff._opt_state["m"]
    if "__pipeline__" in m:  # a pipeline strategy keeps its blocks stacked
        m = ff._adapt_weight_layout(m)
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32) * scale, m)


def check_first_step(ctx, ff, seed: int, inputs, labels,
                     controls=()) -> dict:
    """{"program": {grad.<group>: rel_l2}}: the program's first-step
    gradient of every parameter against the float32 reference's, by
    group, on the seed's weights and first batch.  Each precision in
    ``controls`` adds the reference compared with itself that much
    lower (`sweep` and the tests; never a benchmark run)."""
    fam, cfg = ctx.family, ctx.cfg
    stacked = fam.make_weights(cfg, seed, "reference")
    ids, lab = jnp.asarray(inputs["input"]), jnp.asarray(labels)
    micro = ctx.traffic["reference_micro_batch"]
    want = fam.reference_grads(stacked, ids, lab, "float32", micro)

    def against_reference(got):
        stats = check.group_rel_l2(got, want, fam.GROUPS)
        return {f"grad.{k}": v for k, v in stats.items()}

    # (on several chips the moments are spread over the mesh; the
    # reference lives on the first chip)
    out = {"program": against_reference(jax.device_put(
        fam.to_reference_layout(first_step_grads(ff, cfg), cfg),
        ctx.devices[0]))}
    for precision in controls:
        out[precision] = against_reference(
            fam.reference_grads(stacked, ids, lab, precision, micro))
    return out


def bring_up(ctx):
    """Graph and `FFModel.compile`: (ff, global batch)."""
    fam, cfg, tr = ctx.family, ctx.cfg, ctx.traffic
    chips = len(ctx.devices)
    batch = tr["batch_per_chip"] * chips
    with ctx.span("build_graph"):
        ff = fam.build_model(cfg, batch, tr["seq"], chips)
    with ctx.span("ffmodel_compile"):
        fam.compile_model(ff, cfg, ctx.devices)
    ctx.out(f"mesh={dict(zip(ff.mesh.axis_names, ff.mesh.devices.shape))} "
            f"batch={batch} seq={tr['seq']}")
    return ff, batch


def first_step(ctx, ff, seed: int, batch: int):
    """The seed's weights into the program, its batches, one step."""
    fam, cfg, tr = ctx.family, ctx.cfg, ctx.traffic
    with ctx.span("make_weights"):
        ff.set_weights(fam.make_weights(cfg, seed, "program"))
    rng = np.random.default_rng(seed)
    batches = [fam.make_batch(cfg, batch, tr["seq"], rng, one_label=not i)
               for i in range(tr["distinct_batches"])]
    with ctx.span("first_step"):
        jax.block_until_ready(ff.train_step(*batches[0])["loss"])
    return batches


def sweep(ctx, seeds, control_seeds):
    """Rule 3's readings in one process: per seed the statistics of the
    program at the stated precision, and for ``control_seeds`` those of
    the reference one precision down (``control``) and, where that is
    not float32 itself, at the stated precision (for scale).  Resets
    Adam's moments between seeds, which no benchmark run does."""
    from benchmarks.reference import control_precisions

    ff, batch = bring_up(ctx)
    names = control_precisions(ctx.cfg["precision"])
    for seed in seeds:
        ff._opt_state = jax.tree.map(jnp.zeros_like, ff._opt_state)
        batches = first_step(ctx, ff, seed, batch)
        stats = check_first_step(
            ctx, ff, seed, *batches[0],
            controls=tuple(names) if seed in control_seeds else ())
        yield {"seed": seed, **{names.get(k, k): v
                                for k, v in stats.items()}}


class Ticker(threading.Thread):
    """A thread that only sleeps ``every`` seconds at a time and keeps
    the longest it was away: where that is as long as the driver's
    longest gap between dispatches, the whole process stood still (the
    machine's cores are shared); where it is not, only the thread that
    feeds the device waited, inside the program or the runtime."""

    def __init__(self, every: float = 0.02):
        super().__init__(daemon=True)
        self.every, self.longest_ms, self._halt = every, 0.0, False

    def run(self):
        last = time.monotonic()
        while not self._halt:
            time.sleep(self.every)
            now = time.monotonic()
            self.longest_ms = max(self.longest_ms, 1e3 * (now - last))
            last = now

    def stop(self) -> float:
        self._halt = True
        self.join()
        return self.longest_ms


def window(ctx, ff, batches, seconds: float, in_flight: int) -> dict:
    """The timed window: `train_step` on the batches in turn until
    ``seconds`` have passed, then one wait for the last step.  The host
    keeps ``in_flight`` steps queued ahead of the device, or as many as
    the runtime lets it (11 on the v5e), so a stall of the host shorter
    than that many steps leaves the device fed and the reading alone
    (``tools/stall_probe.py`` shows it; PERF.md section 2).  Returns the
    steps, the seconds, the losses and how the feeding went:
    ``starved`` dispatches found the device's queue empty, ``deepest``
    is the most steps the host got ahead, ``host_gap`` the longest time
    from one dispatch to the next with where it went."""
    losses = []
    ticker = Ticker()
    ticker.start()
    t0 = time.monotonic()
    # with --trace 1 the profiler is open from a third of the way in,
    # for trace_seconds of steady steps
    trace_at = seconds / 3 if ctx.trace else float("inf")
    trace_from = None  # (step, seconds) while the profiler is open
    steps = done = starved = deepest = 0
    gap, last, d_step = {"ms": 0.0}, t0, 0.0

    def stop_trace():
        jax.block_until_ready(losses[-1])
        ctx.trace_stop()
        ctx.counters["traced_steps"] = steps - trace_from[0]

    while True:
        now = time.monotonic() - t0
        if now >= seconds:
            break
        if now >= trace_at:
            jax.block_until_ready(losses)
            ctx.trace_start()
            trace_from, trace_at = (steps, now), float("inf")
        elif trace_from and now - trace_from[1] >= ctx.traffic["trace_seconds"]:
            stop_trace()
            trace_from = None
        # how far ahead of the device the host is (the program returns
        # each step's loss as a device array; `is_ready` asks, no wait)
        while done < steps and losses[done].is_ready():
            done += 1
        starved += steps > 0 and done == steps
        deepest = max(deepest, steps - done)
        inputs, labels = batches[steps % len(batches)]
        t = time.monotonic()
        if 1e3 * (t - last) > gap["ms"]:  # the longest turn of this loop
            gap = {"ms": 1e3 * (t - last), "at_s": last - t0,
                   "in_train_step_ms": 1e3 * d_step}
        last = t
        losses.append(ff.train_step(inputs, labels)["loss"])
        d_step = time.monotonic() - t
        steps += 1
        if steps > in_flight:
            jax.block_until_ready(losses[steps - 1 - in_flight])
    jax.block_until_ready(losses[-1])
    if trace_from:
        stop_trace()
    return {"steps": steps, "window_s": time.monotonic() - t0,
            "losses": losses, "starved": int(starved), "deepest": deepest,
            "host_gap": gap, "ticker_gap_ms": ticker.stop()}


def run(ctx) -> dict:
    tr = ctx.traffic
    ff, batch = bring_up(ctx)
    batches = first_step(ctx, ff, ctx.seed, batch)
    names = {}
    if ctx.args.controls:  # the builder's sweeps; decides nothing
        from benchmarks.reference import control_precisions

        names = control_precisions(ctx.cfg["precision"])
    try:
        with ctx.span("check"):
            checked = check_first_step(ctx, ff, ctx.seed, *batches[0],
                                       controls=tuple(names))
    except Exception as e:  # rule 4: a check that cannot be made fails
        ctx.out(f"check could not be made: {type(e).__name__}: {e}")
        checked = {"program": {}}
    stats = checked["program"]
    for precision, name in names.items():
        ctx.out(f"{name} ({precision}) " + json.dumps(checked.get(precision)))
    with ctx.span("warm_up"):
        for inputs, labels in batches[1:3]:
            m = ff.train_step(inputs, labels)
        jax.block_until_ready(m["loss"])

    # the program's own gauge: 0 where no search ran (every cell today)
    ctx.counters["search_ms"] = ff.telemetry.metrics.gauge(
        "compile/search_ms").value

    before = ctx.watch.snapshot()["lowered"]
    setup_s = time.monotonic() - ctx.t_process_start
    w = window(ctx, ff, batches, ctx.seconds, tr["in_flight"])
    compiles = ctx.watch.snapshot()["lowered"] - before

    steps, window_s = w["steps"], w["window_s"]
    values = np.asarray(jax.device_get(w["losses"]), np.float64)
    failed = int(np.count_nonzero(~np.isfinite(values)))
    tokens = steps * batch * tr["seq"]
    ctx.counters.update(steps=steps, window_s=window_s,
                        step_ms=1e3 * window_s / steps, batch=batch)
    ctx.out(f"window: {steps} steps in {window_s:.3f} s, "
            f"{1e3 * window_s / steps:.3f} ms/step, first loss "
            f"{values[0]:.4f}, last {values[-1]:.4f}, non-finite {failed}")
    ctx.out(f"feed: at most {w['deepest']} steps ahead of the device, "
            f"{w['starved']} dispatches found its queue empty, longest "
            "gap between dispatches " + json.dumps(
                {k: round(v, 1) for k, v in w["host_gap"].items()})
            + f", longest the ticker thread was away "
              f"{w['ticker_gap_ms']:.1f} ms")
    return {
        "end_to_end": {
            "train_tokens_per_s_per_chip":
                tokens / window_s / len(ctx.devices),
            "setup_s": setup_s,
        },
        "attempted": steps, "failed": failed, "stats": stats,
        "compiles_in_window": compiles,
    }
