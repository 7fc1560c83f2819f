"""Request-scoped distributed tracing for the serving fleet.

`obs/trace.py` answers "where did this RUN's time go"; this module
answers "where did THIS request's 900 ms TTFT go" across a
disaggregated fleet: one `TraceContext` is minted per admitted request
at the front (sampled by `--trace-sample`) and rides the request
through dispatch, the disagg dispatcher's priced migrate-vs-re-prefill
decision, the FFKV `kv_transfer` fabric (the wire dict travels in the
frame header so the adopting decode replica's spans join the same
tree), each replica's continuous scheduler (prefill / decode phase
spans that REFERENCE the shared per-dispatch batch spans instead of
duplicating them), and the speculative verify rounds.

Spans land in two places:

* the metrics registry's event stream as `"kind":"span"` JSONL records
  (drained into `run_telemetry.jsonl` — the input to
  `tools/trace_analyze.py` and `telemetry_summary.py`'s Tracing
  section), and
* Chrome trace-event "X" events merged into the run's `trace.json`
  (one track per replica: `pid` = replica id, `FRONT_PID` for the
  front), so a cross-replica migration renders as one connected tree
  in Perfetto.

Per-request trees are SAMPLED and off by default, unlike the host
spans of `obs/trace.py`, which are always on: a front built without a
`ReqTracer` (or one whose sampler rejects the request) carries
`req.trace = None`, every hot-path call site guards on that, and the
decode loop allocates no `ReqSpan` (`span_allocations()` counts them;
tests/test_reqtrace.py guards it).  Both kinds of span read one clock,
`time.monotonic()`, and share one id space, so a request's phase span
references the scheduler's per-dispatch span (`sched.*.dispatch`) by
`span_id` instead of wrapping the same call in a second span.
"""
from __future__ import annotations

import itertools
import random
import threading
import time
from typing import Dict, List, Optional

from .trace import next_span_id

# the front's Perfetto track; replica spans use pid = replica id (>= 0)
FRONT_PID = -1

__all__ = ["FRONT_PID", "ReqSpan", "TraceContext", "ReqTracer",
           "NullReqTracer", "NULL_REQTRACER", "span_allocations"]

# every ReqSpan construction bumps it; an unsampled request never does
_SPAN_ALLOCS = 0


def span_allocations() -> int:
    """How many ReqSpan objects have been constructed process-wide."""
    return _SPAN_ALLOCS


class ReqSpan:
    """One timed span in a request's trace tree.  `end()` is
    idempotent: the first call stamps `t_end` and records the span,
    later calls (e.g. the context's finish() sweep over still-open
    spans) are no-ops."""

    __slots__ = ("tracer", "trace_id", "span_id", "parent_id", "name",
                 "pid", "t_start", "t_end", "args")

    def __init__(self, tracer: "ReqTracer", trace_id: Optional[str],
                 span_id: int, parent_id: Optional[int], name: str,
                 pid: int, args: Dict):
        global _SPAN_ALLOCS
        _SPAN_ALLOCS += 1
        self.tracer = tracer
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.pid = pid
        self.args = args
        self.t_start = tracer.now()
        self.t_end: Optional[float] = None

    def end(self, **args) -> None:
        if self.t_end is not None:
            return
        if args:
            self.args.update(args)
        self.t_end = self.tracer.now()
        self.tracer._record(self)

    def __enter__(self) -> "ReqSpan":
        return self

    def __exit__(self, exc_type, exc, tb):
        self.end()
        return False


class TraceContext:
    """One request's trace: a root span plus a name->open-span registry
    so begin/end pairs can straddle threads (admission happens on the
    caller, dispatch on the dispatcher thread, phase spans on replica
    worker threads).  `finish()` force-ends anything still open so a
    failed/shed request never leaves a dangling span."""

    def __init__(self, tracer: "ReqTracer", trace_id: str, name: str,
                 pid: int, args: Dict):
        self.tracer = tracer
        self.trace_id = trace_id
        self._lock = threading.Lock()
        self.root = tracer._span(trace_id, None, name, pid, args)
        self._open: Dict[str, ReqSpan] = {}

    # -- span lifecycle ---------------------------------------------------
    def begin(self, name: str, pid: Optional[int] = None,
              parent: Optional[int] = None, **args) -> ReqSpan:
        """Open a named span (child of the root unless `parent` given).
        Re-opening a still-open name ends the stale one first — the
        registry holds at most one open span per name."""
        span = self.tracer._span(
            self.trace_id,
            self.root.span_id if parent is None else parent,
            name,
            self.root.pid if pid is None else pid,
            args,
        )
        with self._lock:
            stale = self._open.pop(name, None)
            self._open[name] = span
        if stale is not None:
            stale.end(truncated=True)
        return span

    def end(self, name: str, **args) -> None:
        with self._lock:
            span = self._open.pop(name, None)
        if span is not None:
            span.end(**args)

    def annotate(self, name: str, **args) -> None:
        """Merge attributes into a still-open named span (e.g. the
        disagg dispatcher stamping cost terms onto the dispatch span)."""
        with self._lock:
            span = self._open.get(name)
        if span is not None:
            span.args.update(args)

    def open_id(self, name: str) -> Optional[int]:
        with self._lock:
            span = self._open.get(name)
        return span.span_id if span is not None else None

    def wire(self, parent: Optional[int] = None,
             pid: Optional[int] = None) -> Dict:
        """JSON-safe context for a frame header: the adopting side's
        spans join this tree via `ReqTracer.begin_remote`."""
        return {
            "trace_id": self.trace_id,
            "parent": self.root.span_id if parent is None else parent,
            "pid": self.root.pid if pid is None else pid,
        }

    def finish(self, **args) -> None:
        """End the root span (and force-end any still-open children)."""
        with self._lock:
            dangling = list(self._open.values())
            self._open.clear()
        for span in dangling:
            span.end()
        self.root.end(**args)


class ReqTracer:
    """Mints sampled per-request trace contexts and collects finished
    spans: each one is pushed into the registry's event stream as a
    `"kind":"span"` record (draining into run_telemetry.jsonl) and
    kept in memory for Chrome trace.json export."""

    enabled = True

    def __init__(self, registry=None, sample: float = 1.0, seed: int = 0,
                 max_spans: int = 200_000):
        if not 0.0 <= sample <= 1.0:
            raise ValueError(f"trace sample must be in [0, 1], got {sample}")
        self.registry = registry
        self.sample = float(sample)
        self.max_spans = int(max_spans)
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        self._trace_ids = itertools.count(1)
        self.spans: List[Dict] = []
        self.traces_started = 0
        self.spans_recorded = 0
        self.spans_dropped = 0

    now = staticmethod(time.monotonic)

    # -- trace/span construction ------------------------------------------
    def trace(self, name: str = "request", pid: int = FRONT_PID,
              **args) -> Optional[TraceContext]:
        """A new per-request context, or None when the sampler rejects
        the request (the caller then carries `trace=None` and every
        downstream call site stays allocation-free)."""
        if self.sample <= 0.0:
            return None
        if self.sample < 1.0:
            with self._lock:
                keep = self._rng.random() < self.sample
            if not keep:
                return None
        with self._lock:
            self.traces_started += 1
            tid = f"req-{next(self._trace_ids):06d}"
        return TraceContext(self, tid, name, pid, args)

    def _span(self, trace_id: Optional[str], parent_id: Optional[int],
              name: str, pid: int, args: Dict) -> ReqSpan:
        return ReqSpan(self, trace_id, next_span_id(), parent_id,
                       name, pid, args)

    def shared_span(self, span, pid: int) -> None:
        """Record a finished per-dispatch host span (`obs.trace.span`:
        `sched.prefill.dispatch`, `sched.decode.dispatch`,
        `sched.spec.verify.dispatch`) that served EVERY traced request
        in the batch: it belongs to no single trace (trace_id None) and
        per-request spans reference it by span id."""
        self._push(span, None, None, pid)

    def begin_remote(self, wire: Optional[Dict], name: str,
                     pid: Optional[int] = None, **args
                     ) -> Optional[ReqSpan]:
        """Adopt a wire dict (from `TraceContext.wire`, e.g. out of an
        FFKV frame header) — the new span joins the originating tree."""
        if not wire or "trace_id" not in wire:
            return None
        return self._span(
            wire["trace_id"], wire.get("parent"), name,
            int(wire.get("pid", FRONT_PID)) if pid is None else int(pid),
            args)

    # -- sinks --------------------------------------------------------------
    def _record(self, span: ReqSpan) -> None:
        self._push(span, span.trace_id, span.parent_id, span.pid)

    def _push(self, span, trace_id: Optional[str],
              parent_id: Optional[int], pid: int) -> None:
        rec = {
            "kind": "span",
            "name": span.name,
            "trace_id": trace_id,
            "span_id": span.span_id,
            "parent_id": parent_id,
            "pid": pid,
            "t_start_us": round(span.t_start * 1e6, 1),
            "dur_us": round((span.t_end - span.t_start) * 1e6, 1),
            "args": span.args,
        }
        with self._lock:
            if len(self.spans) < self.max_spans:
                self.spans.append(rec)
                self.spans_recorded += 1
            else:
                self.spans_dropped += 1
                return
        if self.registry is not None:
            self.registry.span(rec)

    def chrome_events(self) -> List[Dict]:
        """Finished spans as Chrome trace-event "X" (complete) events:
        one track per replica (`pid` = replica id; the front is
        FRONT_PID) plus process_name metadata naming the tracks."""
        with self._lock:
            spans = list(self.spans)
        events: List[Dict] = []
        pids = set()
        for rec in spans:
            pids.add(rec["pid"])
            args = dict(rec["args"])
            if rec["trace_id"] is not None:
                args["trace_id"] = rec["trace_id"]
            args["span_id"] = rec["span_id"]
            if rec["parent_id"] is not None:
                args["parent_id"] = rec["parent_id"]
            events.append({
                "ph": "X",
                "name": rec["name"],
                "cat": "reqtrace",
                "ts": rec["t_start_us"],
                "dur": rec["dur_us"],
                "pid": rec["pid"],
                "tid": 0,
                "args": args,
            })
        for pid in sorted(pids):
            label = "front" if pid == FRONT_PID else f"replica {pid}"
            events.append({
                "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
                "ts": 0.0, "args": {"name": f"serving {label}"},
            })
        return events

    def stats(self) -> Dict:
        with self._lock:
            return {
                "sample": self.sample,
                "traces_started": self.traces_started,
                "spans_recorded": self.spans_recorded,
                "spans_dropped": self.spans_dropped,
            }


class NullReqTracer:
    """Disabled request tracer: every method is a constant-time no-op
    that allocates nothing — `trace()` returns None, so downstream
    `req.trace is not None` guards all fall through."""

    enabled = False
    sample = 0.0

    def trace(self, name: str = "request", pid: int = FRONT_PID,
              **args) -> None:
        return None

    def begin_remote(self, wire, name, pid=None, **args) -> None:
        return None

    def chrome_events(self) -> List[Dict]:
        return []

    def stats(self) -> Dict:
        return {"sample": 0.0, "traces_started": 0,
                "spans_recorded": 0, "spans_dropped": 0}


NULL_REQTRACER = NullReqTracer()
