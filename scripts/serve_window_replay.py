#!/usr/bin/env python3
"""Replay a serving cell's window in scheduler iterations, on the CPU,
and say how `serve_tokens_per_s` depends on the iteration's time:

    python3 scripts/serve_window_replay.py --traffic context-turns \
        --slots 32 --chunk 8 --from-ms 35 --to-ms 40

`serve_tokens_per_s` counts the requests seen to complete inside a
wall-clock window (`benchmarks/drivers/serve.py`), so it moves in steps
of one completion; a cell's trace is fixed (`shape_seed`), and above a
knee the schedule is fixed too, in ITERATIONS: a request holds its slot
for ceil(prompt / chunk) + new - 1 of them on the one-pass plan, and a
freed slot is refilled by the next admission (the front hands the
backlog's head over inside the completion).  What a run adds is the
time an iteration takes, so the count is a step function of that time:
this prints its plateaus and, at one time, the completions nearest the
window's ends.  A cell is steady where the plateau round its own
iteration time (`gap_p50_ms` of a run's `window` line) is wider than
the time's spread between processes and machines (about +-0.5 %, my
chip runs, PR 52); PERF.md §7 has the story of cell 9.

A model of the scheduler, not the scheduler: FIFO admission with the
pool never full, one time for every pass and one for every plain decode
step (`--step-ratio`), no stalls.  It placed cell 9's completions
within 0.1 s over 37 s and read cells 4, 7, 9 and 10 to the token
(my chip runs, PR 52); cell 5's reads 0.4 tokens/s off.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from drivers.serve import make_schedule  # noqa: E402


def replay(tr, slots, chunk, pass_s, step_s, seconds, refill_after=0,
           rows=None):
    """[(t_done, new tokens)] of every request of the trace, from the
    run's start.  `refill_after` 1 leaves a freed slot empty for an
    iteration (the dispatcher's lost race, before PR 52).  `rows` (a
    list) collects, an iteration, `(t at its start, [(length, tokens
    fed) a slot, None where idle])`."""
    reqs = [(due, len(prompt), new)
            for due, prompt, new in make_schedule(tr, 1, seconds, 1000)]
    t, it, nxt, held = 0.0, 0, 0, 0
    backlog, queue, done = [], [], []
    live = [None] * slots  # [prompt tokens left, new tokens left, new]
    while nxt < len(reqs) or backlog or queue or any(live):
        while nxt < len(reqs) and reqs[nxt][0] <= t:
            backlog.append(nxt)
            nxt += 1
        while backlog and held < slots:  # an arrival that finds room
            queue.append((it, backlog.pop(0)))
            held += 1
        for s in range(slots):
            if live[s] is None and queue and queue[0][0] <= it:
                _, i = queue.pop(0)
                live[s] = [reqs[i][1], reqs[i][2], reqs[i][2], reqs[i][1]]
        if not any(live):
            if nxt == len(reqs):
                break
            t = max(t, reqs[nxt][0])
            continue
        feeding = any(row is not None and row[0] > 1 for row in live)
        if rows is not None:  # prompt fed so far + tokens sampled so far
            rows.append((t, [None if row is None else (
                row[3] - row[0] + row[2] - row[1],
                min(chunk, row[0]) if feeding and row[0] else 1)
                for row in live]))
        t += pass_s if feeding else step_s
        it += 1
        for s, row in enumerate(live):
            if row is None:
                continue
            if row[0] > 0:  # its feed's last token is sampled in place
                row[0] -= min(chunk, row[0]) if feeding else 1
                if row[0] == 0:
                    row[1] -= 1
            else:
                row[1] -= 1
            if row[0] == 0 and row[1] == 0:
                done.append((t, row[2]))
                live[s], held = None, held - 1
                if backlog:
                    queue.append((it + refill_after, backlog.pop(0)))
                    held += 1
    return done


def window(tr, done, seconds):
    """(tokens/s, completions in the window, [(t - t0, tokens)])."""
    done = sorted(done)
    t0 = done[tr["warm_completions"] - 1][0]
    rel = [(t - t0, n) for t, n in done]
    inside = [n for t, n in rel if 0 < t <= seconds]
    return sum(inside) / seconds, len(inside), rel


def window_rows(traffic: str, slots: int, chunk: int, pass_ms: float,
                seconds: float = 30.0, step_ratio: float = 0.65):
    """The rows of the iteration at the middle of `traffic`'s window,
    at `pass_ms` an iteration: ([length a slot, 0 where idle], [tokens
    fed a slot]): the mix of lengths a probe times a pass at."""
    tr = json.load(open(os.path.join(ROOT, "benchmarks", "traffic",
                                     traffic + ".json")))
    rows = []
    done = sorted(replay(tr, slots, chunk, pass_ms / 1e3,
                         step_ratio * pass_ms / 1e3, seconds, rows=rows))
    middle = done[tr["warm_completions"] - 1][0] + seconds / 2
    _, at = min(rows, key=lambda r: abs(r[0] - middle))
    return ([0 if r is None else r[0] for r in at],
            [0 if r is None else r[1] for r in at])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", required=True,
                    help="a name under benchmarks/traffic/")
    ap.add_argument("--slots", type=int, required=True)
    ap.add_argument("--chunk", type=int, required=True)
    ap.add_argument("--from-ms", type=float, required=True)
    ap.add_argument("--to-ms", type=float, required=True)
    ap.add_argument("--every-ms", type=float, default=0.05)
    ap.add_argument("--step-ratio", type=float, default=0.65,
                    help="a plain decode step's time over a pass's")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--refill-after", type=int, default=0)
    ap.add_argument("--at-ms", type=float,
                    help="also list the completions near the window's ends")
    args = ap.parse_args()
    tr = json.load(open(os.path.join(
        ROOT, "benchmarks", "traffic", args.traffic + ".json")))

    def read(ms):
        done = replay(tr, args.slots, args.chunk, ms / 1e3,
                      args.step_ratio * ms / 1e3, args.seconds,
                      args.refill_after)
        return window(tr, done, args.seconds)

    first = last = None
    for ms in np.arange(args.from_ms, args.to_ms + 1e-9, args.every_ms):
        rate, n, _ = read(ms)
        if last is not None and abs(rate - last[0]) > 1e-9:
            print(f"{first:7.2f} .. {ms - args.every_ms:7.2f} ms  "
                  f"{last[0]:9.3f} tokens/s  {last[1]} completions")
            first = None
        if first is None:
            first = ms
        last = (rate, n)
    print(f"{first:7.2f} .. {args.to_ms:7.2f} ms  {last[0]:9.3f} tokens/s  "
          f"{last[1]} completions")
    if args.at_ms:
        _, _, rel = read(args.at_ms)
        for name, lo, hi in (("opening", -0.5, 0.5), ("end", args.seconds - 1,
                                                      args.seconds + 1)):
            print(f"near the {name}: " + " ".join(
                f"{t:.2f}:{n}" for t, n in rel if lo < t < hi))
    return 0


if __name__ == "__main__":
    sys.exit(main())
