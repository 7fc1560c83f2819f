#!/usr/bin/env python
"""Per-request critical-path breakdown from run_telemetry.jsonl span
records (obs/reqtrace.py; docs/OBSERVABILITY.md "Request tracing").

Usage:
    python tools/trace_analyze.py <run_telemetry.jsonl | trace-dir>
        [--slowest N] [--check]

Groups `"kind":"span"` records into per-request trace trees, buckets
each tree's time into the serving phases (queue / dispatch / prefill /
migration / kv_adopt / decode / spec_verify — the last from the shared
verify-round batch spans the per-request decode span references), and
prints p50/p99 per phase plus the N slowest requests with their phase
split.  --check exits non-zero when any tree is disconnected (orphan
spans / missing root) — the serving_trace bench leg's assertion runs
through the same functions.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

#: phase bucket order for reports (spec_verify is informational — it
#: overlaps the decode phase rather than extending the critical path)
PHASES = ("queue", "dispatch", "prefill", "migration", "kv_adopt",
          "decode", "spec_verify")
#: the scheduler's host span around one verify dispatch, shared by the
#: traced requests that rode it
SPEC_VERIFY_SPAN = "sched.spec.verify.dispatch"


def load_records(path: str) -> List[Dict]:
    """Parse a telemetry JSONL (or the trace dir holding one).  Bad
    lines are skipped here — telemetry_summary.py owns strict torn-
    line reporting; this tool only needs the span records."""
    if os.path.isdir(path):
        path = os.path.join(path, "run_telemetry.jsonl")
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return out


def build_traces(records: List[Dict]
                 ) -> Tuple[Dict[str, List[Dict]], Dict[int, Dict]]:
    """(traces, batch_spans): spans grouped by trace_id, plus the
    shared dispatch spans (trace_id None — `sched.prefill.dispatch` /
    `sched.decode.dispatch` / `sched.spec.verify.dispatch`) indexed by
    span_id for ref resolution."""
    traces: Dict[str, List[Dict]] = {}
    batch: Dict[int, Dict] = {}
    for rec in records:
        if rec.get("kind") != "span":
            continue
        tid = rec.get("trace_id")
        if tid is None:
            batch[rec["span_id"]] = rec
        else:
            traces.setdefault(tid, []).append(rec)
    return traces, batch


def check_connected(spans: List[Dict]) -> Tuple[bool, List[Dict]]:
    """One tree per trace: exactly one root (parent_id None) and every
    other span's parent present IN this trace.  Returns (ok, orphans)
    — cross-replica spans (kv_adopt arriving via the FFKV frame
    header's wire dict) must resolve like any local child."""
    ids = {s["span_id"] for s in spans}
    roots = [s for s in spans if s.get("parent_id") is None]
    orphans = [s for s in spans
               if s.get("parent_id") is not None
               and s["parent_id"] not in ids]
    return len(roots) == 1 and not orphans, orphans


def phase_breakdown(spans: List[Dict], batch: Dict[int, Dict]
                    ) -> Dict[str, float]:
    """Phase -> microseconds for one trace.  Direct phase spans sum
    by name (a requeued request owns several queue spans); the
    spec_verify bucket sums the shared verify-round batch spans this
    trace's phase spans reference by span id."""
    out: Dict[str, float] = {}
    for s in spans:
        name = s["name"]
        if name in PHASES:
            out[name] = out.get(name, 0.0) + float(s.get("dur_us", 0.0))
        for ref in (s.get("args") or {}).get("batch_spans") or ():
            b = batch.get(ref)
            if b is not None and b["name"] == SPEC_VERIFY_SPAN:
                out["spec_verify"] = (out.get("spec_verify", 0.0)
                                      + float(b.get("dur_us", 0.0)))
    return out


def trace_total_us(spans: List[Dict]) -> float:
    roots = [s for s in spans if s.get("parent_id") is None]
    if roots:
        return float(roots[0].get("dur_us", 0.0))
    return sum(float(s.get("dur_us", 0.0)) for s in spans
               if s["name"] in PHASES)


def _pct(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def analyze(records: List[Dict]) -> Dict:
    """The report data main() renders (and tests/bench assert on):
    per-phase percentiles, per-trace totals + breakdowns, and the
    connectivity verdicts."""
    traces, batch = build_traces(records)
    per_phase: Dict[str, List[float]] = {p: [] for p in PHASES}
    rows = []
    disconnected = []
    for tid, spans in traces.items():
        ok, orphans = check_connected(spans)
        if not ok:
            disconnected.append((tid, orphans))
        phases = phase_breakdown(spans, batch)
        for p, us in phases.items():
            per_phase[p].append(us)
        root = next((s for s in spans if s.get("parent_id") is None),
                    None)
        rows.append({
            "trace_id": tid,
            "total_us": trace_total_us(spans),
            "spans": len(spans),
            "phases": phases,
            "args": dict((root or {}).get("args") or {}),
            "connected": ok,
        })
    rows.sort(key=lambda r: -r["total_us"])
    n_spans = sum(r["spans"] for r in rows)
    summary = {}
    for p in PHASES:
        vals = sorted(per_phase[p])
        if vals:
            summary[p] = {
                "traces": len(vals),
                "p50_us": _pct(vals, 0.50),
                "p99_us": _pct(vals, 0.99),
                "total_us": sum(vals),
            }
    return {
        "traces": len(rows),
        "spans": n_spans,
        "batch_spans": len(batch),
        "phases": summary,
        "requests": rows,
        "disconnected": disconnected,
    }


def _ms(us: float) -> str:
    return f"{us / 1e3:.2f}"


def render(report: Dict, slowest: int = 3) -> str:
    lines = []
    n = report["traces"]
    spans_per = report["spans"] / n if n else 0.0
    lines.append(
        f"Request traces: {n}  (spans {report['spans']}, "
        f"{spans_per:.1f}/trace; shared batch spans "
        f"{report['batch_spans']})")
    if report["disconnected"]:
        lines.append(
            f"DISCONNECTED traces: "
            f"{[tid for tid, _ in report['disconnected']]}")
    if report["phases"]:
        lines.append("")
        lines.append(f"{'phase':<12}{'traces':>8}{'p50 ms':>10}"
                     f"{'p99 ms':>10}{'total ms':>11}")
        for p in PHASES:
            st = report["phases"].get(p)
            if not st:
                continue
            lines.append(
                f"{p:<12}{st['traces']:>8}{_ms(st['p50_us']):>10}"
                f"{_ms(st['p99_us']):>10}{_ms(st['total_us']):>11}")
    top = report["requests"][:max(0, slowest)]
    if top:
        lines.append("")
        lines.append(f"Slowest {len(top)}:")
        for r in top:
            args = r["args"]
            ok = args.get("ok")
            head = (f"  {r['trace_id']}  total {_ms(r['total_us'])} ms"
                    f"  spans={r['spans']}")
            if ok is not None:
                head += f"  ok={ok}"
            if not r["connected"]:
                head += "  DISCONNECTED"
            lines.append(head)
            split = "  |  ".join(
                f"{p} {_ms(r['phases'][p])}"
                for p in PHASES if p in r["phases"])
            if split:
                lines.append(f"    {split}")
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("path", help="run_telemetry.jsonl or the trace dir")
    p.add_argument("--slowest", type=int, default=3, metavar="N",
                   help="show the N slowest requests (default 3)")
    p.add_argument("--check", action="store_true",
                   help="exit non-zero if any trace tree is "
                        "disconnected (orphan spans / missing root)")
    args = p.parse_args(argv)
    try:
        records = load_records(args.path)
    except FileNotFoundError as e:
        print(f"error: no telemetry file at {e}", file=sys.stderr)
        return 1
    report = analyze(records)
    if report["traces"] == 0:
        print("no span records found (tracing off, or sampled out "
              "via --trace-sample)")
        return 0
    sys.stdout.write(render(report, slowest=args.slowest))
    if args.check and report["disconnected"]:
        print(f"error: {len(report['disconnected'])} disconnected "
              "trace tree(s)", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
