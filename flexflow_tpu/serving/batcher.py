"""Dynamic request batching (the Triton scheduler role: coalesce
concurrent single requests into one device batch, bounded by
max_batch_size and a flush timeout).

Two-stage pipeline: the ASSEMBLER thread drains the request queue,
concatenates up to max_batch samples, and *dispatches* the jitted
forward (jax dispatch is asynchronous, so this returns immediately);
the COMPLETER thread materializes results and scatters them back to
waiters.  While batch N computes on the device, batch N+1 is being
assembled and dispatched — device and host time overlap instead of
serializing, the same double-buffering the dataloader uses for
training.  Per-request latency (submit -> result ready) is tracked in a
ring buffer; `latency_stats()` reports p50/p95/p99.
"""
from __future__ import annotations

import queue
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np


def percentile_summary(values, ps=(0.50, 0.95, 0.99)) -> Dict[str, float]:
    """n / p*_ms / mean_ms summary of latencies in SECONDS — the one
    percentile implementation (batchers' ring windows, the continuous
    scheduler's TTFT stats and the fronts' all use it)."""
    lats = sorted(values)
    if not lats:
        return {"n": 0}

    def pct(p):
        # nearest-rank: ceil(p*n)-th order statistic (int(p*n) is
        # upward-biased — p95 of a 20-sample window would always be
        # the max)
        import math

        i = min(len(lats) - 1, max(0, math.ceil(p * len(lats)) - 1))
        return lats[i] * 1e3

    out = {"n": len(lats)}
    for p in ps:
        out[f"p{int(round(p * 100))}_ms"] = round(pct(p), 3)
    out["mean_ms"] = round(sum(lats) / len(lats) * 1e3, 3)
    return out


def latency_percentiles(latencies, lock) -> Dict[str, float]:
    """p50/p95/p99/mean (ms) over a ring buffer (shared by the forward
    and generation batchers)."""
    with lock:  # appends race from the worker threads
        vals = list(latencies)
    return percentile_summary(vals)


class _Pending:
    __slots__ = ("inputs", "event", "result", "error", "t_submit")

    def __init__(self, inputs):
        self.inputs = inputs
        self.event = threading.Event()
        self.result: Optional[np.ndarray] = None
        self.error: Optional[Exception] = None
        self.t_submit = time.monotonic()

    # -- future-style API (infer_async) ---------------------------------
    def wait(self, timeout: Optional[float] = None) -> np.ndarray:
        if not self.event.wait(timeout):
            raise TimeoutError("inference request timed out")
        if self.error is not None:
            raise self.error
        return self.result


class DynamicBatcher:
    """Assembler + completer threads around an InferenceEngine."""

    def __init__(self, engine, max_batch: int = 32,
                 flush_timeout_s: float = 0.005,
                 max_inflight: int = 2,
                 latency_window: int = 1024, registry=None):
        self.engine = engine
        self.max_batch = max_batch
        # obs.metrics registry: counters/latencies fold in as
        # serving/infer_* so they drain to run_telemetry.jsonl
        # (the /v2/stats JSON shape is unchanged)
        self.registry = registry
        self.flush_timeout_s = flush_timeout_s
        self._queue: "queue.Queue[_Pending]" = queue.Queue()
        # bounded: backpressure keeps at most `max_inflight` batches on
        # the device while the assembler keeps building the next one
        self._inflight: "queue.Queue" = queue.Queue(maxsize=max_inflight)
        self._stop = threading.Event()
        self._latencies = deque(maxlen=latency_window)
        self._lat_lock = threading.Lock()
        self._carry: Optional[_Pending] = None  # overflow from coalescing
        self._carry_lock = threading.Lock()  # close() vs assembler
        self.batches_run = 0
        self.requests_done = 0
        self._assembler = threading.Thread(target=self._assemble_loop,
                                           daemon=True)
        self._completer = threading.Thread(target=self._complete_loop,
                                           daemon=True)
        self._assembler.start()
        self._completer.start()

    # -- client API -----------------------------------------------------
    def infer(self, inputs: Dict[str, np.ndarray],
              timeout: Optional[float] = 30.0) -> np.ndarray:
        """Blocking single/partial-batch request; thread-safe."""
        return self.infer_async(inputs).wait(timeout)

    def infer_async(self, inputs: Dict[str, np.ndarray]) -> _Pending:
        """Non-blocking submit; returns a future-style handle with
        .wait(timeout).  Raises after close() — the assembler is gone
        and the request would otherwise wait out its full timeout."""
        if self._stop.is_set():
            raise RuntimeError("DynamicBatcher is closed")
        p = _Pending({k: np.asarray(v) for k, v in inputs.items()})
        self._queue.put(p)
        # enqueue-then-recheck: close() may have finished its final
        # drain between the check above and the put — fail the request
        # ourselves rather than park it for its full wait timeout
        # (idempotent if the drain also saw it)
        if self._stop.is_set():
            p.error = RuntimeError("DynamicBatcher is closed")
            p.event.set()
        return p

    @property
    def worker_alive(self) -> bool:
        """False once either pipeline thread has died — /v2/health
        reports "degraded" then (requests would only time out)."""
        return self._assembler.is_alive() and self._completer.is_alive()

    def latency_stats(self) -> Dict[str, float]:
        """p50/p95/p99/mean request latency (ms) over the ring window."""
        return latency_percentiles(self._latencies, self._lat_lock)

    def close(self):
        self._stop.set()

        def drain():
            with self._carry_lock:
                p, self._carry = self._carry, None
            if p is not None:
                p.error = RuntimeError("DynamicBatcher closed")
                p.event.set()
            for q in (self._queue, self._inflight):
                while True:
                    try:
                        item = q.get_nowait()
                    except queue.Empty:
                        break
                    pendings = [item] if isinstance(item, _Pending) \
                        else item[1]
                    for p in pendings:
                        p.error = RuntimeError("DynamicBatcher closed")
                        p.event.set()

        # a worker stuck in a cold-bucket compile can outlive the join
        # timeout and enqueue AFTER a one-shot drain — keep draining
        # until both threads are really gone (bounded), then once more
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline and (
            self._assembler.is_alive() or self._completer.is_alive()
        ):
            drain()
            self._assembler.join(timeout=0.2)
            self._completer.join(timeout=0.2)
        drain()

    # -- assembler stage ------------------------------------------------
    def _assemble_loop(self):
        while not self._stop.is_set():
            with self._carry_lock:
                first, self._carry = self._carry, None
            if first is None:
                try:
                    first = self._queue.get(timeout=0.05)
                except queue.Empty:
                    continue
            batch: List[_Pending] = [first]
            total = len(next(iter(first.inputs.values())))
            # never coalesce past what one jitted forward can take, or
            # the dispatch degrades to the synchronous chunked path
            cap = min(self.max_batch, self.engine.chunk_cap())
            # absolute deadline from the FIRST request, so a steady
            # trickle can't defer the flush past the configured bound
            deadline = time.monotonic() + self.flush_timeout_s
            while total < cap:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                try:
                    nxt = self._queue.get(timeout=remaining)
                except queue.Empty:
                    break
                n = len(next(iter(nxt.inputs.values())))
                if total + n > cap:
                    with self._carry_lock:
                        if self._stop.is_set():
                            # close() already drained; fail it here
                            # rather than parking it forever
                            nxt.error = RuntimeError("DynamicBatcher closed")
                            nxt.event.set()
                        else:
                            self._carry = nxt  # overflow: heads next batch
                    break
                batch.append(nxt)
                total += n
            self._dispatch(batch)

    def _dispatch(self, batch: List[_Pending]):
        try:
            keys = list(batch[0].inputs.keys())
            joined = {
                k: np.concatenate([p.inputs[k] for p in batch]) for k in keys
            }
            n = len(next(iter(joined.values())))
            if n > self.engine.chunk_cap():
                # single oversize request: engine.infer chunks it
                # synchronously (coalescing never builds past the cap)
                self._scatter(batch, self.engine.infer(joined))
                return
            dev_out = self.engine.dispatch(joined, n)  # async launch
            self._inflight.put((dev_out, batch, n))  # blocks at capacity
        except Exception as e:
            for p in batch:
                p.error = e
                p.event.set()

    # -- completer stage ------------------------------------------------
    def _complete_loop(self):
        while not self._stop.is_set() or not self._inflight.empty():
            try:
                dev_out, batch, n = self._inflight.get(timeout=0.05)
            except queue.Empty:
                continue
            try:
                self._scatter(batch, np.asarray(dev_out)[:n])  # waits
            except Exception as e:
                for p in batch:
                    p.error = e
                    p.event.set()

    def _scatter(self, batch: List[_Pending], out: np.ndarray):
        """Slice a completed batch back to its waiters + account."""
        self.batches_run += 1
        start = 0
        now = time.monotonic()
        for p in batch:
            k = len(next(iter(p.inputs.values())))
            p.result = out[start:start + k]
            start += k
            with self._lat_lock:
                self._latencies.append(now - p.t_submit)
            self.requests_done += 1
            p.event.set()
        if self.registry is not None:
            reg = self.registry
            reg.counter("serving/infer_batches_run").inc()
            reg.counter("serving/infer_requests_done").inc(len(batch))
            for p in batch:
                reg.histogram("serving/infer_latency_ms").observe(
                    (now - p.t_submit) * 1e3)
