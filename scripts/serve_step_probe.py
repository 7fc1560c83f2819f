#!/usr/bin/env python3
"""On the chip: a serving cell's two step programs, called directly at
the real size BEFORE the first benchmark run (PR 35: a hang inside the
harness says nothing).

    chiprun --chips 1 -- python3 scripts/serve_step_probe.py \
        --workload <cell> [--calls 10] [--chunks 8,64,128] \
        [--position 2048 | --positions mix] [--scopes] \
        [--selected-read walk,gather,view]

Builds the cell's server as the harness does (`build_server`, the
seed's weights, `PagedKVDecodeModel` with the front's arguments), gives
every slot a row at mid length on blocks of its own, and calls the
decode step and the prefill program: the first call's seconds (trace,
lower, compile or cache load), then `--calls` more, timed to the
logits' arrival (host clock around a blocking call: dispatch included).
Prints the weight tree's parameters and bytes, the pool's bytes, the
device's `memory_stats` and one JSON line.  `--chunks` does that once
for each `prefill_chunk` of the list in turn (the weights stay, the
twin and its state are built anew), a JSON line each: the readings a
configuration's `prefill_chunk` is chosen from.  `--position` puts the
rows at another length than half the table's, `--positions mix` each
row at its length in the iteration at the middle of the cell's window
with the tokens it is fed there (`scripts/serve_window_replay.py
window_rows` over the cell's traffic: a pass whose cost follows the
rows' lengths reads otherwise at one length for all); `--scopes` also traces
three calls of each program and prints where their device time went by
the program's names (`benchmarks/device_scopes.py`).  For a family
whose latent attention reads picked keys (`ops/mla.py`),
`--selected-read` times each chunk once a formulation of that read,
forced on both step programs: `walk` (the kernel over the row's live
pages with the picks as its mask), `gather` (`pool[table[idx // page],
idx % page]`, a `[b, s, k, width]` gather), `view` (the row's view
gathered once over the table's width and the picks as a mask on dense
scores), `plan` (the default: what the op's own rule takes for each
step length).  A serving family only."""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import run as harness  # noqa: E402  (benchmarks/run.py)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=3000000019)
    ap.add_argument("--chunks", default=None,
                    help="prefill_chunk values to probe in turn "
                         "(default: the configuration's own)")
    ap.add_argument("--position", type=int, default=None,
                    help="every row's length (default: half the table)")
    ap.add_argument("--positions", choices=["mix"], default=None,
                    help="mix: the rows' lengths and fed tokens of the "
                         "iteration at the middle of the cell's window")
    ap.add_argument("--pass-ms", type=float, default=114.6,
                    help="the iteration's time the mix is replayed at "
                         "(default: cell 12's accepted pass)")
    ap.add_argument("--scopes", action="store_true",
                    help="trace three calls a program: the by-scope table")
    ap.add_argument("--selected-read", default="plan",
                    help="formulations of the selected read to time in "
                         "turn: plan (the op's rule), walk, gather, view")
    args = ap.parse_args()
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = harness.load_json(os.path.join(ROOT, entry["file"]))
    fam = harness.load_module("families", cfg["family"])
    out = {"device": jax.devices()[0].device_kind}
    t0 = time.monotonic()
    ff = fam.build_server(cfg, jax.devices()[:1])
    ff.set_weights(fam.make_weights(cfg, args.seed, "program"))
    leaves = jax.tree.leaves(ff._weights)
    out["parameters"] = int(sum(x.size for x in leaves))
    out["weight_bytes"] = int(sum(x.nbytes for x in leaves))
    chunks = ([int(x) for x in args.chunks.split(",")] if args.chunks
              else [ff.config.prefill_chunk])
    mix = None
    if args.positions == "mix":
        from serve_window_replay import window_rows

        mix = window_rows(cell["traffic"], ff.config.serving_slots,
                          ff.config.prefill_chunk, args.pass_ms)
        out["positions"] = "mix"
    for read in args.selected_read.split(","):
        for chunk in chunks:
            try:
                with selected_read(read):
                    probe(ff, chunk, dict(out, selected_read=read),
                          args.calls, t0, args.position, args.scopes, mix)
            except Exception as e:  # a formulation that does not fit
                if read == "plan":
                    raise
                print(json.dumps({"selected_read": read,
                                  "prefill_chunk": chunk,
                                  "failed": f"{type(e).__name__}: "
                                            f"{str(e)[:300]}"}), flush=True)
            t0 = time.monotonic()
    return 0


@contextlib.contextmanager
def selected_read(formulation: str):
    """`MLAttention.selected_plan` answering `formulation` ("walk",
    "gather" or "view") for every step length while the block runs,
    "plan" the op's own rule: the formulations of the selected read are
    the op's, the probe's readings are the constants of its rule."""
    from flexflow_tpu.ops.mla import MLAttention

    if formulation == "plan":
        yield
        return
    if formulation not in ("walk", "gather", "view"):
        raise SystemExit(f"--selected-read: no formulation {formulation!r}")
    own = MLAttention.selected_plan
    MLAttention.selected_plan = lambda self, s, n: formulation
    try:
        yield
    finally:
        MLAttention.selected_plan = own


def probe(ff, prefill_chunk: int, out: dict, calls: int, t0: float,
          position=None, scopes: bool = False, mix=None) -> None:
    """One twin at `prefill_chunk`: its two programs timed, its line.
    `mix`: ([length a slot], [tokens fed a slot]) in place of one
    `position` for every row."""
    from flexflow_tpu.serving.scheduler import PagedKVDecodeModel

    c = ff.config
    model = PagedKVDecodeModel(
        ff, batch_slots=c.serving_slots, page_size=c.kv_page_size,
        num_blocks=c.kv_pool_blocks or None, devices=jax.devices()[:1],
        prefill_chunk=prefill_chunk, prefix_cache=c.prefix_cache)
    out["prefill_chunk"] = model.prefill_chunk
    out["build_s"] = round(time.monotonic() - t0, 1)
    out["paged_kernel"] = model.paged_kernel
    out["loop"] = model.loop
    out["kv_block_bytes"] = model.kv_block_bytes
    out["pool_bytes"] = model.kv_block_bytes * model.num_blocks
    b, width = model.batch_slots, model.max_blocks_per_seq
    # every slot mid-sequence on blocks of its own
    table = 1 + np.arange(b * width, dtype=np.int32).reshape(b, width) \
        % (model.num_blocks - 1)
    feeds = None
    if mix is not None:
        pos = np.minimum(np.asarray(mix[0], np.int32),
                         model.max_seq - model.prefill_chunk)
        feeds = np.minimum(np.asarray(mix[1], np.int32),
                           model.prefill_chunk)
        out["live_keys"], out["rows_fed"] = int(pos.sum()), feeds.tolist()
    else:
        pos = np.full((b,), model.max_seq // 2 if position is None
                      else position, np.int32)
    tokens = np.arange(1, b + 1, dtype=np.int32)
    rows = (np.ones((b,), np.int32),) if model.has_slot_state else ()

    def timed(call):
        t = time.monotonic()
        call()
        jax.block_until_ready(model._state)
        return time.monotonic() - t

    def decode():
        return model.step(tokens, pos, table, *rows)

    chunk = np.tile(tokens[:, None], (1, model.prefill_chunk))
    # (the one-pass program takes `row_tokens` whatever the family)
    fed = ((np.full((b,), model.prefill_chunk, np.int32)
            if feeds is None else feeds),) \
        if model.has_slot_state or model.prefill_passes == 1 else ()

    def prefill():
        return model.prefill_step(chunk, pos, table, *fed)

    for name, call in (("step", decode), ("prefill", prefill)):
        out[f"{name}_first_call_s"] = round(timed(call), 2)
        times = [timed(call) for _ in range(calls)]
        out[f"{name}_ms"] = round(1e3 * float(np.median(times)), 3)
        print(f"{name}: first {out[f'{name}_first_call_s']} s, then "
              + " ".join(f"{1e3 * t:.2f}" for t in times) + " ms",
              flush=True)
    if scopes:
        scope_table(decode, prefill, model, prefill_chunk)
    logits = decode()
    out["logits_finite"] = bool(np.isfinite(logits).all())
    if model.exit_last is not None:
        out["exit_pdf_row0"] = [round(float(x), 4)
                                for x in model.exit_last[0]]
    stats = jax.devices()[0].memory_stats() or {}
    out["memory"] = {k: int(stats[k]) for k in (
        "bytes_in_use", "peak_bytes_in_use", "bytes_limit") if k in stats}
    print(json.dumps(out), flush=True)


def scope_table(decode, prefill, model, chunk: int) -> None:
    """Three traced calls of each program, then the by-scope table."""
    from benchmarks import device_scopes

    trace_dir = os.path.join(ROOT, "benchmarks", "out",
                             f"step_probe_{chunk}")
    with jax.profiler.trace(trace_dir):
        for call in (decode, prefill):
            for _ in range(3):
                call()
            jax.block_until_ready(model._state)
    device_scopes.main(["", harness.find_xplane(trace_dir)])


if __name__ == "__main__":
    sys.exit(main())
