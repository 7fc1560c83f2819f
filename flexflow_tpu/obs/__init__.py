"""obs — unified run telemetry (tracing, metrics, fidelity).

Three pillars (docs/OBSERVABILITY.md), and `scopes`, the grammar of the
names the program gives its device work:

  * `trace`    — the one span primitive (`obs.trace.span`): always on,
                 into a bounded ring and the jax profiler's host plane;
                 dumped as a Chrome trace when a `trace_dir` is set;
  * `metrics`  — typed counters/gauges/histograms unifying search stats,
                 resilience counters and PerfMetrics into one JSONL;
  * `fidelity` — per-run predicted-vs-measured step-time records.

`RunTelemetry` bundles them per-FFModel, wired through FFConfig
(`trace_dir`, `profile_steps`, `telemetry`) / CLI (`--trace-dir`,
`--profile-steps`, `--telemetry`).  Spans are recorded whatever the
configuration says; `enabled` decides whether files are written, the
library's log records are captured, the fidelity record is computed and
per-request traces are sampled.
"""
from __future__ import annotations

import logging
import os
import time
import weakref
from typing import Dict, Optional, Tuple

from .fidelity import fidelity_record, report_fidelity
from .metrics import (
    MetricsRegistry,
    TelemetryLogHandler,
    emit_counters,
    registry_of,
)
from .reqtrace import (
    FRONT_PID,
    NULL_REQTRACER,
    NullReqTracer,
    ReqTracer,
    TraceContext,
)
from .trace import Tracer, span

TRACE_FILENAME = "trace.json"
TELEMETRY_FILENAME = "run_telemetry.jsonl"


def parse_profile_steps(spec: Optional[str]) -> Optional[Tuple[int, int]]:
    """`--profile-steps start:count` -> (first step, one-past-last);
    raises ValueError on malformed specs (validated at config time)."""
    if not spec:
        return None
    parts = spec.split(":")
    if len(parts) != 2:
        raise ValueError(
            f"profile_steps must be 'start:count', got {spec!r}"
        )
    try:
        start, count = int(parts[0]), int(parts[1])
    except ValueError:
        raise ValueError(
            f"profile_steps must be 'start:count' integers, got {spec!r}"
        ) from None
    if start < 0 or count < 1:
        raise ValueError(
            f"profile_steps needs start >= 0 and count >= 1, got {spec!r}"
        )
    return start, start + count


class RunTelemetry:
    """Per-run telemetry bundle: tracer + metrics registry + artifact
    paths.  The metrics registry and the span ring's writer always
    exist (searches/supervisors fold their counters unconditionally —
    one dict walk per run); the on-disk artifacts only when enabled."""

    def __init__(
        self,
        trace_dir: Optional[str] = None,
        enabled: Optional[bool] = None,
        profile_steps: Optional[str] = None,
        run_id: Optional[str] = None,
        trace_sample: float = 1.0,
    ):
        self.trace_dir = trace_dir
        self.enabled = bool(trace_dir) if enabled is None else bool(enabled)
        self.run_id = run_id or f"run-{int(time.time())}-{os.getpid()}"
        self.metrics = MetricsRegistry()
        self.tracer = Tracer(run_id=self.run_id)
        # per-request serving traces (obs/reqtrace.py): spans drain into
        # the same registry/JSONL and merge into trace.json at flush()
        self.reqtrace = (
            ReqTracer(registry=self.metrics, sample=trace_sample)
            if self.enabled else NULL_REQTRACER
        )
        self.profile_window = parse_profile_steps(profile_steps)
        self._profiling = False
        self._log_handler: Optional[TelemetryLogHandler] = None
        self._detach = None
        if self.enabled:
            # capture flexflow_tpu.* log records (calibration failures,
            # supervisor notices) into the run's JSONL; explicit
            # telemetry opt-in also opts the library logger into INFO
            # when the app left it unconfigured (NOTSET would gate the
            # records out before the handler ever saw them).  The
            # handler detaches on close() or GC (weakref.finalize), so
            # per-model telemetry can't pile handlers onto the shared
            # logger for the process lifetime.  NOTE: logging is
            # process-global — two concurrently LIVE traced models each
            # capture the library's log stream (records aren't
            # attributable to a run without contextvars).
            self._log_handler = TelemetryLogHandler(self.metrics)
            lib_logger = logging.getLogger("flexflow_tpu")
            lib_logger.addHandler(self._log_handler)
            if lib_logger.level == logging.NOTSET:
                lib_logger.setLevel(logging.INFO)
            self._detach = weakref.finalize(
                self, lib_logger.removeHandler, self._log_handler
            )

    @classmethod
    def from_config(cls, cfg) -> "RunTelemetry":
        return cls(
            trace_dir=getattr(cfg, "trace_dir", None),
            enabled=(
                bool(getattr(cfg, "trace_dir", None))
                or bool(getattr(cfg, "telemetry", False))
            ),
            profile_steps=getattr(cfg, "profile_steps", None),
            trace_sample=getattr(cfg, "trace_sample", 1.0),
        )

    # -- jax profiler window --------------------------------------------
    def on_step(self, step: int) -> None:
        """Drive the optional `jax.profiler.trace` capture window around
        the configured [start, stop) steps (every `fit` step; a no-op
        without a window)."""
        if self.profile_window is None or self.trace_dir is None:
            return
        start, stop = self.profile_window
        if step == start and not self._profiling:
            import jax

            os.makedirs(self.trace_dir, exist_ok=True)
            jax.profiler.start_trace(
                os.path.join(self.trace_dir, "jax_profile")
            )
            self._profiling = True
        elif step >= stop and self._profiling:
            self._stop_profiler()

    def _stop_profiler(self) -> None:
        import jax

        jax.profiler.stop_trace()
        self._profiling = False

    # -- artifacts -------------------------------------------------------
    @property
    def trace_path(self) -> Optional[str]:
        return (
            os.path.join(self.trace_dir, TRACE_FILENAME)
            if self.trace_dir else None
        )

    @property
    def telemetry_path(self) -> Optional[str]:
        return (
            os.path.join(self.trace_dir, TELEMETRY_FILENAME)
            if self.trace_dir else None
        )

    def flush(self) -> Dict[str, str]:
        """Write/refresh the run artifacts: the Chrome trace JSON (the
        span ring since this run began, bounded by the ring) and the
        telemetry JSONL (append of newly drained records).  No-op when
        disabled or no trace_dir is set."""
        if self._profiling:  # a fit that ended inside the window
            self._stop_profiler()
        if not self.enabled or not self.trace_dir:
            return {}
        os.makedirs(self.trace_dir, exist_ok=True)
        self.tracer.write(self.trace_path,
                          extra_events=self.reqtrace.chrome_events())
        self.metrics.write_jsonl(self.telemetry_path)
        return {"trace": self.trace_path, "telemetry": self.telemetry_path}

    def close(self) -> None:
        """Flush and detach the log handler (idempotent)."""
        self.flush()
        if self._detach is not None:
            self._detach()  # weakref.finalize: safe to call twice
        self._log_handler = None


__all__ = [
    "FRONT_PID",
    "MetricsRegistry",
    "NULL_REQTRACER",
    "NullReqTracer",
    "ReqTracer",
    "RunTelemetry",
    "TraceContext",
    "TELEMETRY_FILENAME",
    "TRACE_FILENAME",
    "Tracer",
    "emit_counters",
    "fidelity_record",
    "parse_profile_steps",
    "registry_of",
    "report_fidelity",
    "span",
]
