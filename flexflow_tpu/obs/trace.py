"""Host spans: one primitive, always on, two sinks, one clock.

`span(name, **args)` is a context manager, usable from any thread, that
on every call

* enters a `jax.profiler.TraceAnnotation(name, **args)`: whenever anyone
  has the profiler open (`--profile-steps`, `jax.profiler.start_server`,
  a benchmark's traced stretch) the span lands on the xplane's host
  plane, on the line of the thread that made it and on the device
  trace's clock, with its args as stats;
* appends one record to a process-wide bounded ring, stamped with
  `time.monotonic()` (the clock the serving handles' `t_submit` /
  `t_first_token` / `t_done` use), with its parent from a per-thread
  stack.

No switch turns it off.  Contract: a span costs two clock reads, one
inactive TraceMe and one deque append, 1.5 to 2 microseconds measured
(PR 26, a loop of 100,000; tests/test_host_spans.py keeps a loose
guard), and no span sits inside a per-row or per-token loop.

`trace_dir` decides only whether `Tracer.write` dumps the ring to a
Chrome trace-event file (Perfetto / chrome://tracing), never whether a
span is recorded.  The device side has its own names: `obs/scopes.py`.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque, namedtuple
from typing import Dict, Iterable, List, Optional

from jax.profiler import TraceAnnotation

#: records the ring keeps.  One whole run of the largest benchmark cell
#: makes some 10^4 (12 a scheduler iteration); 65,536 are a few MB.
RING_SIZE = 65536

#: one finished span; times are `time.monotonic()` seconds
SpanRecord = namedtuple(
    "SpanRecord",
    "span_id parent_id name thread t_start t_end args")

_ring: deque = deque(maxlen=RING_SIZE)  # plain tuples, SpanRecord's order
_ids = itertools.count(1)
_local = threading.local()
_now = time.monotonic
_thread = threading.get_ident


def next_span_id() -> int:
    """The process-wide span id space (request spans draw from it too,
    so a per-request span can reference a dispatch span by id)."""
    return next(_ids)


class span:
    """One timed host span; see the module docstring."""

    __slots__ = ("name", "args", "span_id", "parent_id", "t_start",
                 "t_end", "_ann")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args

    def __enter__(self) -> "span":
        try:
            stack = _local.stack
        except AttributeError:
            stack = _local.stack = []
        self.parent_id = stack[-1] if stack else None
        self.span_id = sid = next(_ids)
        stack.append(sid)
        self._ann = ann = TraceAnnotation(self.name, **self.args)
        ann.__enter__()
        self.t_start = _now()
        return self

    def set(self, **args) -> None:
        """Counts known only once the work is done (tokens emitted,
        requests admitted): into the record and the xplane event.
        After the span has ended (counts that a later fetch brings for
        a dispatch left on the device) into the ring's record alone:
        the xplane event is closed."""
        self.args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __exit__(self, exc_type, exc, tb):
        self.t_end = t_end = _now()
        self._ann.__exit__(exc_type, exc, tb)
        self._ann = None
        _local.stack.pop()
        _ring.append((self.span_id, self.parent_id, self.name, _thread(),
                      self.t_start, t_end, self.args))
        return False


def spans() -> List[SpanRecord]:
    """The ring's records, oldest first (a span is appended when it
    ends, so a parent follows its children)."""
    return [SpanRecord._make(r) for r in list(_ring)]


def self_time(records: Iterable[SpanRecord]) -> Dict[int, float]:
    """span_id -> seconds: a span's duration less the part of it that
    its children (among ``records``) cover."""
    records = list(records)
    children: Dict[int, list] = {}
    for r in records:
        if r.parent_id is not None:
            children.setdefault(r.parent_id, []).append(
                (r.t_start, r.t_end))
    out = {}
    for r in records:
        covered, end = 0.0, r.t_start
        for s, e in sorted(children.get(r.span_id, ())):
            s, e = max(s, end), min(e, r.t_end)
            if e > s:
                covered += e - s
                end = e
        out[r.span_id] = (r.t_end - r.t_start) - covered
    return out


def chrome_events(records: Iterable[SpanRecord],
                  pid: Optional[int] = None) -> List[Dict]:
    """Records as Chrome trace-event "X" (complete) events; ``ts`` is
    the monotonic clock in microseconds."""
    pid = os.getpid() if pid is None else pid
    events = []
    for r in records:
        args = dict(r.args, span_id=r.span_id)
        if r.parent_id is not None:
            args["parent_id"] = r.parent_id
        events.append({
            "ph": "X", "name": r.name, "cat": "span",
            "ts": r.t_start * 1e6, "dur": (r.t_end - r.t_start) * 1e6,
            "pid": pid, "tid": r.thread, "args": args,
        })
    return events


class Tracer:
    """A run's writer of the ring: `write` dumps, as one Chrome
    trace-event document, every span that ended since this object was
    made (the ring is process-wide; the run began here)."""

    def __init__(self, run_id: Optional[str] = None):
        self.run_id = run_id
        self._since = _now()

    def write(self, path: str, extra_events=None) -> None:
        """`extra_events` merges pre-built events (the request tracer's
        per-replica tracks) into the same document, sorted by time."""
        events = chrome_events(
            r for r in spans() if r.t_end >= self._since)
        if extra_events:
            events.extend(extra_events)
        events.sort(key=lambda e: e["ts"])
        doc = {"traceEvents": events, "displayTimeUnit": "ms"}
        if self.run_id:
            doc["otherData"] = {"run_id": self.run_id}
        tmp = f"{path}.tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
