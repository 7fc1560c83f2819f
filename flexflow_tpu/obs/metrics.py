"""Metrics registry: typed counters/gauges/histograms + a JSONL drain.

Unifies the repo's three previously disjoint observability surfaces —
the searches' `EvalStats`/`strategy.search_stats` dicts, the resilience
supervisor's `resilience_logger` counters, and `PerfMetrics` epoch
summaries — into one registry that drains to a per-run
`run_telemetry.jsonl` with a stable schema (SCHEMA_VERSION below; see
docs/OBSERVABILITY.md).

Record schema, one JSON object per line:

    {"schema": 1, "ts": <unix seconds>, "kind": "counter" | "gauge" |
     "histogram" | "event" | "fidelity" | "span", "name": <str>, ...payload}

    counter   -> {"value": int}
    gauge     -> {"value": float}
    histogram -> {"count", "sum", "min", "max", "mean"
                  [, "exemplar": {"value", "trace_id"}]}
    event     -> {"fields": {...}}   (log records, one-shot markers)
    fidelity  -> the obs/fidelity.py record verbatim
    span      -> a request-trace span (obs/reqtrace.py): {"trace_id",
                 "span_id", "parent_id", "pid", "t_start_us", "dur_us",
                 "args"}
"""
from __future__ import annotations

import json
import logging
import time
from typing import Dict, List, Optional

SCHEMA_VERSION = 1


class Counter:
    """Monotonic cumulative count."""

    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0

    def inc(self, n: int = 1) -> None:
        if n < 0:
            raise ValueError(f"counter {self.name}: negative increment {n}")
        self.value += n

    def record(self) -> Dict:
        return {"kind": "counter", "name": self.name, "value": self.value}


class Gauge:
    """Last-write-wins scalar."""

    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = float(v)

    def record(self) -> Dict:
        return {"kind": "gauge", "name": self.name, "value": self.value}


class Histogram:
    """Streaming count/sum/min/max summary of observations.

    An observation may carry an **exemplar** (a trace_id from
    obs/reqtrace.py): the histogram keeps the worst (largest) sampled
    value's exemplar per drain window, so an SLO regression in e.g.
    `serving/ttft_ms` links straight to the offending request's trace.
    The exemplar resets at drain; count/sum stay cumulative."""

    __slots__ = ("name", "count", "sum", "min", "max",
                 "exemplar_value", "exemplar_trace")

    def __init__(self, name: str):
        self.name = name
        self.count = 0
        self.sum = 0.0
        self.min = float("inf")
        self.max = float("-inf")
        self.exemplar_value = float("-inf")
        self.exemplar_trace: Optional[str] = None

    def observe(self, v: float, exemplar: Optional[str] = None) -> None:
        v = float(v)
        self.count += 1
        self.sum += v
        if v < self.min:
            self.min = v
        if v > self.max:
            self.max = v
        if exemplar is not None and v > self.exemplar_value:
            self.exemplar_value = v
            self.exemplar_trace = exemplar

    @property
    def mean(self) -> float:
        return self.sum / self.count if self.count else 0.0

    def reset_exemplar(self) -> None:
        self.exemplar_value = float("-inf")
        self.exemplar_trace = None

    def record(self) -> Dict:
        rec = {
            "kind": "histogram",
            "name": self.name,
            "count": self.count,
            "sum": self.sum,
            "min": self.min if self.count else 0.0,
            "max": self.max if self.count else 0.0,
            "mean": self.mean,
        }
        if self.exemplar_trace is not None:
            rec["exemplar"] = {"value": self.exemplar_value,
                               "trace_id": self.exemplar_trace}
        return rec


class MetricsRegistry:
    """Create-or-get typed metrics; same-name different-type is a bug
    and raises."""

    def __init__(self):
        self._metrics: Dict[str, object] = {}
        self._events: List[Dict] = []

    def _get(self, name: str, cls):
        m = self._metrics.get(name)
        if m is None:
            m = self._metrics[name] = cls(name)
        elif not isinstance(m, cls):
            raise TypeError(
                f"metric {name!r} already registered as "
                f"{type(m).__name__}, not {cls.__name__}"
            )
        return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str) -> Histogram:
        return self._get(name, Histogram)

    def remove(self, name: str) -> None:
        """Drop a metric whose subject is gone (e.g. a retired serving
        replica's per-id gauge) — per-entity names minted from
        monotonically increasing ids would otherwise accumulate
        without bound in a long-lived process."""
        self._metrics.pop(name, None)

    # -- bulk folds ------------------------------------------------------
    def fold_counters(self, group: str, mapping: Dict) -> None:
        """Snapshot a flat counters dict (search_stats, supervisor
        counters, PerfMetrics fields) as gauges named `group/key` —
        these surfaces report cumulative totals, so last-write-wins is
        the correct fold."""
        for k, v in mapping.items():
            if isinstance(v, bool):
                v = int(v)
            if isinstance(v, (int, float)):
                self.gauge(f"{group}/{k}").set(v)

    def event(self, name: str, **fields) -> None:
        """One-shot structured record (log lines, run markers)."""
        self._events.append({
            "kind": "event",
            "name": name,
            "ts": time.time(),
            "fields": fields,
        })

    def fidelity(self, record: Dict) -> None:
        """Attach a simulator-fidelity record (obs/fidelity.py)."""
        rec = dict(record)
        rec["kind"] = "fidelity"
        rec.setdefault("name", "fidelity")
        rec.setdefault("ts", time.time())
        self._events.append(rec)

    def span(self, record: Dict) -> None:
        """Attach a finished request-trace span (obs/reqtrace.py) to
        the event stream — spans drain exactly once, like events."""
        rec = dict(record)
        rec["kind"] = "span"
        rec.setdefault("ts", time.time())
        self._events.append(rec)

    # -- drain -----------------------------------------------------------
    def drain(self) -> List[Dict]:
        """Buffered events (cleared) + a snapshot of every metric's
        current value.  Each record carries the schema version and a
        timestamp; re-draining re-snapshots metrics (cumulative values,
        later ts wins for readers)."""
        now = time.time()
        records: List[Dict] = []
        events, self._events = self._events, []
        for ev in events:
            ev.setdefault("ts", now)
            ev["schema"] = SCHEMA_VERSION
            records.append(ev)
        for name in sorted(self._metrics):
            metric = self._metrics[name]
            rec = metric.record()
            rec["ts"] = now
            rec["schema"] = SCHEMA_VERSION
            records.append(rec)
            if isinstance(metric, Histogram):
                # exemplars are per-drain-window: the next window's
                # worst sample gets a fresh link
                metric.reset_exemplar()
        return records

    def write_jsonl(self, path: str) -> int:
        """Append drained records to a JSONL file; returns the count."""
        records = self.drain()
        if not records:
            return 0
        with open(path, "a") as f:
            for rec in records:
                f.write(json.dumps(rec) + "\n")
        return len(records)


def _prom_name(name: str) -> str:
    """Registry names (`serving/ttft_ms`) to Prometheus metric names
    (`serving_ttft_ms`): slashes and anything outside [a-zA-Z0-9_:]
    become underscores."""
    out = []
    for ch in name:
        out.append(ch if (ch.isalnum() or ch in "_:") else "_")
    return "".join(out)


def to_prometheus(registry: MetricsRegistry) -> str:
    """Render the live registry as Prometheus text exposition
    (`# TYPE` comments + `name value` samples).  Histograms render as
    summaries (`_count`/`_sum`) plus `_min`/`_max` gauges; a histogram
    holding an exemplar annotates its `_count` sample with the
    OpenMetrics exemplar syntax (`# {trace_id="..."} <value>`) so an
    SLO scrape links to the offending request trace."""
    lines: List[str] = []
    for name in sorted(registry._metrics):
        metric = registry._metrics[name]
        pname = _prom_name(name)
        if isinstance(metric, Counter):
            lines.append(f"# TYPE {pname} counter")
            lines.append(f"{pname} {metric.value}")
        elif isinstance(metric, Gauge):
            lines.append(f"# TYPE {pname} gauge")
            lines.append(f"{pname} {metric.value}")
        elif isinstance(metric, Histogram):
            lines.append(f"# TYPE {pname} summary")
            count_line = f"{pname}_count {metric.count}"
            if metric.exemplar_trace is not None:
                count_line += (
                    f' # {{trace_id="{metric.exemplar_trace}"}}'
                    f" {metric.exemplar_value}")
            lines.append(count_line)
            lines.append(f"{pname}_sum {metric.sum}")
            lines.append(f"# TYPE {pname}_min gauge")
            lines.append(f"{pname}_min {metric.min if metric.count else 0.0}")
            lines.append(f"# TYPE {pname}_max gauge")
            lines.append(f"{pname}_max {metric.max if metric.count else 0.0}")
    return "\n".join(lines) + "\n"


def registry_of(ff) -> Optional[MetricsRegistry]:
    """The model's metrics registry, or None for anything without a
    telemetry bundle (plain executors, tests poking internals)."""
    tel = getattr(ff, "telemetry", None)
    return tel.metrics if tel is not None else None


def emit_counters(logger, label: str, mapping: Dict,
                  registry: Optional[MetricsRegistry] = None,
                  group: Optional[str] = None) -> None:
    """The migration shim for the legacy `RecursiveLogger.counters`
    call sites (mcmc/unity/supervisor): emits the EXACT same log line
    the old call did, then folds the mapping into the registry (when
    one is wired) so the counters also land in run_telemetry.jsonl."""
    logger.counters(label, mapping)
    if registry is not None:
        registry.fold_counters(group or label.replace(" ", "_"), mapping)


class TelemetryLogHandler(logging.Handler):
    """Captures `flexflow_tpu.*` log records (calibration failures,
    supervisor restore notices) into the registry's event stream so
    they land in run_telemetry.jsonl instead of dying on stdout/stderr."""

    def __init__(self, registry: MetricsRegistry, level=logging.INFO):
        super().__init__(level=level)
        self.registry = registry

    def emit(self, record: logging.LogRecord) -> None:
        try:
            self.registry.event(
                "log",
                logger=record.name,
                level=record.levelname,
                message=record.getMessage(),
            )
        except Exception:  # pragma: no cover - never break the app on telemetry
            self.handleError(record)
