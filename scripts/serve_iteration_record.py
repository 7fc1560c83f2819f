#!/usr/bin/env python3
"""One whole run of a serving cell, then WHERE ITS LOOP STOPPED: the
scheduler's record by iteration, read out of the span ring afterwards
(`flexflow_tpu.obs.trace.spans()`), with the collector's passes beside
it.  Nothing in the program or the harness changes: this calls
`benchmarks/run.py`'s `main` with the arguments it is given.

    chiprun --chips 1 -- python3 scripts/serve_iteration_record.py \
        --workload <cell> --seed <n> --seconds 30 --trace 0

Run it from the root of the checkout whose program is to be read (the
program is imported from the working directory, so one copy of this
script reads a parent's tree too).  After the run's own lines it prints
`gc: ...` (collections, their seconds and the longest, by generation),
`iterations: ...` (the passes that STARTED inside the window, the gap
between two starts: median, mean, longest, and the seconds above a
median gap in all) and one `stall ...` line for every gap since the
warm stretch began that is 10 ms and 15 % over the median: where it
fell against the window, which collector passes ran inside it, and
which program span of 36 ms or more (`sched.*`, `model.*`) it lay in.
`--record FILE` (taken off the arguments before the harness sees them)
also writes every dispatch, admission, collector pass and long span as
a JSON line.

Why (PERF.md §6 PR 62, third session; ROADMAP S19): a run's `window`
line gives `gap_p50_ms` and `gap_p95_ms`, which two stops of 0.12 s in
945 passes do not move, and the traced stretch is 3 s of 30; cell 10's
25th completion lands within 0.13 s of its window's end, and two such
stops inside the window (both in `model.fetch_behind`, no collector
pass near them) were what turned 273.267 tokens/s into 263.933 on one
program.
"""
from __future__ import annotations

import gc
import io
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.getcwd())

DISPATCHES = ("sched.prefill.dispatch", "sched.decode.dispatch")
LONG_S = 0.036  # a span this long is not a turn of a healthy loop


class _Tee(io.TextIOBase):
    """stdout, kept: the harness prints `setup_s`, which places the
    window on the spans' clock (`time.monotonic`, as `T_PROCESS_START`)."""

    def __init__(self, out):
        self.out, self.lines = out, []

    def write(self, text):
        self.lines.append(text)
        return self.out.write(text)

    def flush(self):
        self.out.flush()


def main(argv) -> int:
    record = None
    if "--record" in argv:
        i = argv.index("--record")
        record, argv = argv[i + 1], argv[:i] + argv[i + 2:]
    passes, began = [], [0.0]  # (generation, start, seconds, collected)

    def on_gc(phase, info):
        if phase == "start":
            began[0] = time.monotonic()
        else:
            passes.append((info["generation"], began[0],
                           time.monotonic() - began[0], info["collected"]))

    gc.callbacks.append(on_gc)
    from benchmarks import run as harness

    tee = sys.stdout = _Tee(sys.stdout)
    try:
        rc = harness.main(argv)
    finally:
        sys.stdout = tee.out
        gc.callbacks.remove(on_gc)
    from flexflow_tpu.obs.trace import spans

    recs = spans()
    result = next(json.loads(line) for line in reversed(
        "".join(tee.lines).splitlines()) if line.startswith('{"correct"'))
    t0 = (harness.T_PROCESS_START
          + result["metrics"]["setup_s"]["value"])  # the window's opening
    seconds = float(argv[argv.index("--seconds") + 1])
    starts = [r for r in recs if r.name in DISPATCHES]
    admits = [r for r in recs if r.name == "sched.admit"
              and r.args.get("admitted")]
    long_spans = [r for r in recs if r.t_end - r.t_start >= LONG_S
                  and r.name.split(".")[0] in ("sched", "model")
                  and r.name not in DISPATCHES + ("sched.idle_wait",)]
    # the warm stretch begins where the burst is admitted
    warm = next((r.t_start for r in admits if r.args["admitted"] >= 3), t0)
    gaps = [(a, b.t_start - a.t_start) for a, b in zip(starts, starts[1:])
            if a.t_start >= warm]
    inside = [g for a, g in gaps if t0 <= a.t_start < t0 + seconds]
    print("gc: " + json.dumps({
        str(g): {"collections": sum(1 for p in passes if p[0] == g),
                 "seconds": round(sum(p[2] for p in passes if p[0] == g), 3),
                 "longest_ms": round(1e3 * max(
                     (p[2] for p in passes if p[0] == g), default=0.0), 1)}
        for g in (0, 1, 2)}) + f" thresholds {gc.get_threshold()} "
        f"tracked_objects {len(gc.get_objects())}", flush=True)
    if not inside:
        print(f"iterations: none of {len(recs)} spans in the ring starts a "
              "dispatch inside the window (the ring keeps the newest "
              "65,536)", flush=True)
        return rc
    median = statistics.median(inside)
    print("iterations: " + json.dumps({
        "passes_in_window": len(inside),
        "gap_median_ms": round(1e3 * median, 3),
        "gap_mean_ms": round(1e3 * sum(inside) / len(inside), 3),
        "gap_longest_ms": round(1e3 * max(inside), 1),
        "above_median_s": round(sum(g - median for g in inside
                                    if g > median), 3),
        "spans_in_ring": len(recs)}), flush=True)
    for a, g in gaps:
        if g <= max(1.15 * median, median + 0.010):
            continue
        end = a.t_start + g
        print(f"stall {1e3 * (g - median):.1f} ms over a gap, "
              f"{a.t_start - t0:+.2f} s from the window's opening "
              f"({'inside' if t0 <= a.t_start < t0 + seconds else 'outside'}"
              f"); the dispatch's span {1e3 * (a.t_end - a.t_start):.1f} ms; "
              "collector " + json.dumps(
                  [[p[0], round(1e3 * p[2], 1)] for p in passes
                   if p[1] < end and p[1] + p[2] > a.t_start]) + "; in "
              + json.dumps([[r.name, round(1e3 * (r.t_end - r.t_start), 1)]
                            for r in long_spans
                            if r.t_start < end and r.t_end > a.t_start]),
              flush=True)
    if record:
        with open(record, "w") as f:
            for r in sorted(starts + admits + long_spans,
                            key=lambda r: r.t_start):
                f.write(json.dumps({
                    "name": r.name, "t": round(r.t_start - t0, 6),
                    "ms": round(1e3 * (r.t_end - r.t_start), 3),
                    **{k: v for k, v in r.args.items()
                       if isinstance(v, (int, float, str))}}) + "\n")
            for g, start, dt, n in passes:
                if dt >= 2e-3:
                    f.write(json.dumps({
                        "name": "gc", "t": round(start - t0, 6),
                        "ms": round(1e3 * dt, 3), "generation": g,
                        "collected": n}) + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
