#!/usr/bin/env python
"""Render a run_telemetry.jsonl into a step-time / compile-time /
search / resilience / fidelity table.

Usage:
    python tools/telemetry_summary.py <run_telemetry.jsonl | trace-dir>
        [--allow-torn-tail]

Accepts either the JSONL itself or the --trace-dir directory containing
it.  Metrics are cumulative snapshots, so for re-drained runs the
latest record per name wins (ties broken by file order).  See
docs/OBSERVABILITY.md for the record schema.

Unreadable lines are an ERROR, not a silent skip: a summary that
quietly dropped records would misreport the run.  A killed run may
legitimately leave torn line(s) at the FILE TAIL — --allow-torn-tail
tolerates exactly those (reported to stderr with a count); corruption
anywhere else always exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Tuple


class TornTelemetryError(Exception):
    """Unparseable run_telemetry.jsonl line(s).  `bad` holds
    (lineno, detail) pairs; `tail_only` is True when every bad line
    sits after the last good record (a killed-run torn tail)."""

    def __init__(self, bad: List[Tuple[int, str]], tail_only: bool):
        self.bad = bad
        self.tail_only = tail_only
        where = "tail" if tail_only else "mid-file"
        super().__init__(
            f"{len(bad)} unreadable telemetry line(s) ({where}): "
            f"line(s) {[ln for ln, _ in bad]}")


def load_records(path: str, allow_torn_tail: bool = False
                 ) -> Tuple[List[Dict], List[Tuple[int, str]]]:
    """(records, torn_lines).  Raises TornTelemetryError on any
    unparseable line, unless every bad line is at the file tail AND
    `allow_torn_tail` is set — then the torn tail is returned for the
    caller to report."""
    if os.path.isdir(path):
        path = os.path.join(path, "run_telemetry.jsonl")
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    out: List[Dict] = []
    bad: List[Tuple[int, str]] = []
    last_good = 0
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
                last_good = lineno
            except json.JSONDecodeError as e:
                bad.append((lineno, str(e)))
    tail_only = bool(bad) and all(ln > last_good for ln, _ in bad)
    if bad and not (allow_torn_tail and tail_only):
        raise TornTelemetryError(bad, tail_only)
    return out, bad


def latest_by_name(records: List[Dict], kinds) -> Dict[str, Dict]:
    """Last record per name among `kinds` (cumulative snapshots: the
    newest drain supersedes older ones)."""
    out: Dict[str, Dict] = {}
    for rec in records:
        if rec.get("kind") in kinds and "name" in rec:
            out[rec["name"]] = rec
    return out


def _fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


def _section(title: str, rows: List[tuple]) -> str:
    if not rows:
        return ""
    w = max(len(k) for k, _ in rows) + 2
    lines = [title, "-" * len(title)]
    lines += [f"{k:<{w}}{_fmt(v)}" for k, v in rows]
    return "\n".join(lines) + "\n"


def summarize(records: List[Dict]) -> str:
    metrics = latest_by_name(records, {"counter", "gauge", "histogram"})
    fidelity = [r for r in records if r.get("kind") == "fidelity"]
    events = [r for r in records if r.get("kind") == "event"]
    out: List[str] = []

    step = metrics.get("fit/dispatch_ms")
    rows = []
    if step:
        rows += [
            ("steps", step.get("count", 0)),
            ("dispatch ms mean", step.get("mean", 0.0)),
            ("dispatch ms min/max",
             f"{_fmt(step.get('min', 0.0))} / {_fmt(step.get('max', 0.0))}"),
        ]
    epoch = metrics.get("fit/epoch_s")
    if epoch:
        rows.append(("epoch s mean", epoch.get("mean", 0.0)))
    tput = metrics.get("fit/throughput_sps")
    if tput:
        rows.append(("throughput samples/s", tput.get("value", 0.0)))
    # the last epoch's PerfMetrics (fit/metrics/*: counts, loss sums,
    # accuracy)
    rows += [
        (name.split("/", 2)[-1], rec.get("value", 0.0))
        for name, rec in sorted(metrics.items())
        if name.startswith("fit/metrics/")
    ]
    out.append(_section("Steps", rows))

    rows = [
        (name.split("/", 1)[1] if "/" in name else name,
         rec.get("value", 0.0))
        for name, rec in sorted(metrics.items())
        if name.startswith("compile/")
    ]
    fallback = metrics.get("parallel/zero_fallback_leaves")
    if fallback:
        rows.append(("zero fallback leaves", fallback.get("value", 0)))
    out.append(_section("Compile (ms)", rows))

    rows = [
        (name.split("/", 2)[-1], rec.get("value", 0.0))
        for name, rec in sorted(metrics.items())
        if name.startswith("search/")
    ]
    out.append(_section("Search", rows))

    rows = []
    for name, rec in sorted(metrics.items()):
        if not name.startswith("store/") or name.startswith("store/remote_"):
            continue  # remote_* renders under Durability
        short = name.split("/", 1)[1]
        if rec.get("kind") == "histogram":
            # lookup latency: render the streaming summary
            rows.append((
                short,
                f"n={rec.get('count', 0)} mean={_fmt(rec.get('mean', 0.0))} "
                f"min={_fmt(rec.get('min', 0.0))} "
                f"max={_fmt(rec.get('max', 0.0))}",
            ))
        else:
            rows.append((short, rec.get("value", 0.0)))
    out.append(_section("Store", rows))

    rows = [
        (name.split("/", 1)[1], rec.get("value", 0.0))
        for name, rec in sorted(metrics.items())
        if name.startswith("resilience/")
        and not name.startswith("resilience/offload_")
    ]
    out.append(_section("Resilience", rows))

    # the durable offload tier (docs/RESILIENCE.md "Durable offload &
    # host-loss recovery"): upload/verify/degradation counters from the
    # checkpoint mirror plus the strategy store's fleet-mirror traffic
    rows = []
    for name, rec in sorted(metrics.items()):
        if not (name.startswith("resilience/offload_")
                or name.startswith("store/remote_")):
            continue
        short = name.split("/", 1)[1]
        if rec.get("kind") == "histogram":
            rows.append((
                short,
                f"n={rec.get('count', 0)} mean={_fmt(rec.get('mean', 0.0))} "
                f"min={_fmt(rec.get('min', 0.0))} "
                f"max={_fmt(rec.get('max', 0.0))}",
            ))
        else:
            rows.append((short, rec.get("value", 0.0)))
    out.append(_section("Durability", rows))

    # per-tier predicted comm split (topology subsystem,
    # docs/TOPOLOGY.md): ICI vs DCN bytes/time for the compiled
    # strategy's placement — zero DCN on single-slice runs
    rows = [
        (name.split("/", 1)[1], rec.get("value", 0.0))
        for name, rec in sorted(metrics.items())
        if name.startswith("comm/")
    ]
    out.append(_section("Comm", rows))

    # searched-remat memory split (docs/PERF.md "Searched
    # rematerialization"): per-run saved-activation bytes under the
    # compiled plan + the recompute seconds the plan pays
    rows = [
        (name.split("/", 1)[1], rec.get("value", 0.0))
        for name, rec in sorted(metrics.items())
        if name.startswith("mem/") or name == "compute/recompute_s"
    ]
    out.append(_section("Memory", rows))

    rows = []
    # prefix cache (docs/SERVING.md "Prefix cache & chunked prefill"):
    # one composite line ahead of the raw serving/* rows
    hits = metrics.get("serving/prefix_hits")
    hit_toks = metrics.get("serving/prefix_hit_tokens")
    if hits is not None or hit_toks is not None:
        shared = metrics.get("serving/kv_shared_blocks", {})
        evicted = metrics.get("serving/prefix_evictions", {})
        rows.append((
            "prefix cache",
            f"hits={int((hits or {}).get('value', 0))} "
            f"hit_tokens={int((hit_toks or {}).get('value', 0))} "
            f"shared_blocks={int(shared.get('value', 0))} "
            f"evictions={int(evicted.get('value', 0))}",
        ))
    # tensor-parallel replicas (docs/SERVING.md "Tensor-parallel
    # replicas"): one composite line when a multi-chip engine
    # registered its mesh geometry
    tp = metrics.get("serving/tp_degree")
    if tp is not None:
        chips = metrics.get("serving/tp_chips", {})
        per_blk = metrics.get("serving/tp_kv_block_bytes_per_chip", {})
        per_pool = metrics.get("serving/tp_kv_pool_bytes_per_chip", {})
        rows.append((
            "tensor parallel",
            f"degree={int(tp.get('value', 1))} "
            f"chips={int(chips.get('value', 1))} "
            f"kv_block_bytes_per_chip={int(per_blk.get('value', 0))} "
            f"kv_pool_bytes_per_chip={int(per_pool.get('value', 0))}",
        ))
    # disaggregated fleet (docs/SERVING.md "Disaggregated fleet"):
    # one composite line when the dispatcher ever costed a handoff —
    # migrate/re-prefill decisions plus the KV stream counters
    mig = metrics.get("serving/disagg_migrate_decisions")
    rep = metrics.get("serving/disagg_reprefill_decisions")
    if mig is not None or rep is not None:
        done = metrics.get("serving/kv_migration_done", {})
        failed = metrics.get("serving/kv_migration_failed", {})
        mig_bytes = metrics.get("serving/kv_migration_bytes", {})
        mig_blocks = metrics.get("serving/kv_migration_blocks", {})
        rows.append((
            "disaggregated fleet",
            f"migrate={int((mig or {}).get('value', 0))} "
            f"reprefill={int((rep or {}).get('value', 0))} "
            f"migrations_done={int(done.get('value', 0))} "
            f"failed={int(failed.get('value', 0))} "
            f"bytes={int(mig_bytes.get('value', 0))} "
            f"blocks={int(mig_blocks.get('value', 0))}",
        ))
    # fused paged kernel (docs/SERVING.md "Fused paged attention"):
    # one composite read-traffic line when the kernel formulation ran
    blocks = metrics.get("serving/paged_kernel_blocks_read")
    if blocks is not None:
        read = metrics.get("serving/paged_kernel_bytes_read", {})
        avoided = metrics.get("serving/paged_dense_bytes_avoided", {})
        rows.append((
            "paged kernel",
            f"blocks_read={int(blocks.get('value', 0))} "
            f"bytes_read={int(read.get('value', 0))} "
            f"dense_bytes_avoided={int(avoided.get('value', 0))}",
        ))
    # speculative decoding (docs/SERVING.md "Speculative decoding"):
    # accept rate + tokens/round + verify-round rate in one line
    prop = metrics.get("serving/spec_proposed")
    if prop is not None:
        acc = metrics.get("serving/spec_accepted", {})
        rounds = metrics.get("serving/spec_rounds", {})
        per_round = metrics.get("serving/spec_accepted_per_round", {})
        rps = metrics.get("serving/spec_rounds_per_s", {})
        n_prop = int(prop.get("value", 0))
        n_acc = int(acc.get("value", 0))
        rate = n_acc / n_prop if n_prop else 0.0
        rows.append((
            "speculative",
            f"accept_rate={rate:.3f} ({n_acc}/{n_prop}) "
            f"tokens/round={_fmt(per_round.get('mean', 0.0))} "
            f"rounds={int(rounds.get('value', 0))} "
            f"rounds/s={_fmt(rps.get('value', 0.0))}",
        ))
    for name, rec in sorted(metrics.items()):
        if not name.startswith("serving/"):
            continue
        short = name.split("/", 1)[1]
        if rec.get("kind") == "histogram":
            # SLO histograms (ttft_ms, per_token_ms, kv occupancy):
            # render the streaming summary, not a bare value
            rows.append((
                short,
                f"n={rec.get('count', 0)} mean={_fmt(rec.get('mean', 0.0))} "
                f"min={_fmt(rec.get('min', 0.0))} "
                f"max={_fmt(rec.get('max', 0.0))}",
            ))
        else:
            rows.append((short, rec.get("value", 0.0)))
    out.append(_section("Serving", rows))

    # request traces (obs/reqtrace.py, docs/OBSERVABILITY.md "Request
    # tracing"): span counts plus the top-3 slowest requests with
    # their per-phase split — the full report is trace_analyze.py
    try:
        from . import trace_analyze as _ta
    except ImportError:  # run as a script: tools/ itself is on sys.path
        import trace_analyze as _ta
    treport = _ta.analyze(records)
    rows = []
    if treport["traces"]:
        rows += [
            ("traces recorded", treport["traces"]),
            ("spans", treport["spans"]),
            ("spans/trace",
             round(treport["spans"] / treport["traces"], 1)),
            ("shared batch spans", treport["batch_spans"]),
        ]
        if treport["disconnected"]:
            rows.append(("DISCONNECTED trees",
                         len(treport["disconnected"])))
        for r in treport["requests"][:3]:
            split = " ".join(
                f"{p}={r['phases'][p] / 1e3:.2f}ms"
                for p in _ta.PHASES if p in r["phases"])
            rows.append((
                f"slowest {r['trace_id']}",
                f"total={r['total_us'] / 1e3:.2f}ms {split}"))
    out.append(_section("Tracing", rows))

    rows = []
    for rec in fidelity:
        rows += [
            ("source", rec.get("source", "?")),
            ("predicted step ms", rec.get("predicted_step_ms")),
            ("measured step ms", rec.get("measured_step_ms")),
            ("predicted / measured", rec.get("predicted_vs_measured")),
            ("mesh", json.dumps(rec.get("mesh_axes", {}))),
            ("calibrated", rec.get("calibrated", False)),
        ]
    out.append(_section("Fidelity", rows))

    logs = [r for r in events if r.get("name") == "log"]
    if logs:
        lines = ["Log events", "----------"]
        for r in logs[-20:]:
            f = r.get("fields", {})
            lines.append(
                f"[{f.get('level', '?')}] {f.get('logger', '?')}: "
                f"{f.get('message', '')}"
            )
        out.append("\n".join(lines) + "\n")

    body = "\n".join(s for s in out if s)
    return body if body.strip() else "no telemetry records found\n"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("path", help="run_telemetry.jsonl or the trace dir")
    p.add_argument("--allow-torn-tail", action="store_true",
                   help="tolerate unreadable line(s) at the FILE TAIL "
                        "(a killed run's torn write); mid-file "
                        "corruption still exits non-zero")
    args = p.parse_args(argv)
    try:
        records, torn = load_records(
            args.path, allow_torn_tail=args.allow_torn_tail)
    except FileNotFoundError as e:
        print(f"error: no telemetry file at {e}", file=sys.stderr)
        return 1
    except TornTelemetryError as e:
        hint = (" (re-run with --allow-torn-tail to tolerate a "
                "killed run's torn tail)" if e.tail_only else "")
        print(f"error: {e}{hint}", file=sys.stderr)
        return 1
    if torn:
        print(f"warning: skipped {len(torn)} torn tail line(s): "
              f"{[ln for ln, _ in torn]}", file=sys.stderr)
    sys.stdout.write(summarize(records))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
