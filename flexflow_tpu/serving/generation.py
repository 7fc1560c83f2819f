"""Generation serving: KV-cached incremental decoding behind the
batcher/HTTP surface (VERDICT r4 #4 — the scope the reference's
triton/ backend never reached: it is forward-only inference,
triton/README.md:3-6).

`GenerationEngine` owns a decode twin (decoding.make_decoder) of a
trained GPT and runs whole generations as single XLA scan programs
(decoding.run_generate_scan): per-row prompt lengths are a traced
operand, so one compiled program per (total-length bucket, temperature)
serves ANY mix of prompt lengths — concurrent requests with different
prompts coalesce into one device program with zero recompiles.

`GenerationBatcher` is the request coalescer: a worker thread drains
the queue, groups compatible requests (same temperature) up to the
decode batch, runs one scan, and scatters per-request trimmed token
rows back to the waiters.  Latency percentiles ride the same ring
buffer machinery as the forward batcher.
"""
from __future__ import annotations

import queue
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..decoding import _gpt_dims, make_decoder, run_generate_scan
from ..model import FFModel


def _pow2_bucket(n: int, cap: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return min(b, cap)


class GenerationEngine:
    """Batched generation on the KV-cache decode twin of a trained GPT.

    Requests are (prompt, max_new_tokens) pairs; the engine right-pads
    prompts into one [batch, total] buffer (total = the power-of-two
    bucket of the largest plen+max_new, capped at the model's position
    table), runs one scan program, and trims each row to its own
    plen + max_new_tokens (and at eos_id when set)."""

    def __init__(self, ff_train: FFModel, batch_size: int = 8,
                 devices=None, eos_id: int = -1):
        self.ffd = make_decoder(ff_train, batch_size=batch_size,
                                    devices=devices)
        self.batch_size = batch_size
        self.max_seq = _gpt_dims(self.ffd)["max_seq"]
        self.eos_id = eos_id
        self.generations_run = 0

    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens=16, temperature: float = 0.0,
                 seed: int = 0) -> List[List[int]]:
        """prompts: up to batch_size token id lists (any lengths >= 1).
        max_new_tokens: int or per-prompt sequence.  Returns per-prompt
        full token lists (prompt + continuation)."""
        n = len(prompts)
        if not 1 <= n <= self.batch_size:
            raise ValueError(
                f"{n} prompts for a batch-{self.batch_size} engine")
        mnt = (list(max_new_tokens) if not isinstance(max_new_tokens, int)
               else [max_new_tokens] * n)
        if len(mnt) != n:
            raise ValueError("per-prompt max_new_tokens length mismatch")
        plens = [len(p) for p in prompts]
        if min(plens) < 1:
            raise ValueError("empty prompt")
        if max(plens) >= self.max_seq:
            raise ValueError(
                f"prompt length {max(plens)} >= max positions "
                f"{self.max_seq}")
        need = max(p + m for p, m in zip(plens, mnt))
        total = _pow2_bucket(need, self.max_seq)
        buf = np.zeros((self.batch_size, total), np.int32)
        plen_vec = np.ones(self.batch_size, np.int32)  # pad rows: plen 1
        for i, p in enumerate(prompts):
            row = np.asarray(p, np.int32)[:total]
            buf[i, :len(row)] = row
            plen_vec[i] = len(row)
        out = run_generate_scan(self.ffd, buf, plen_vec, temperature, seed)
        self.generations_run += 1
        results = []
        for i in range(n):
            end = min(plens[i] + mnt[i], total)
            row = out[i, :end]
            if self.eos_id >= 0:
                hits = np.flatnonzero(row[plens[i]:] == self.eos_id)
                if hits.size:
                    row = row[:plens[i] + hits[0] + 1]
            results.append(row.tolist())
        return results


class _PendingGen:
    __slots__ = ("prompt", "max_new_tokens", "temperature", "event",
                 "result", "error", "t_submit")

    def __init__(self, prompt, max_new_tokens, temperature):
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.event = threading.Event()
        self.result: Optional[List[int]] = None
        self.error: Optional[Exception] = None
        self.t_submit = time.monotonic()

    def wait(self, timeout: Optional[float] = None) -> List[int]:
        if not self.event.wait(timeout):
            raise TimeoutError("generation request timed out")
        if self.error is not None:
            raise self.error
        return self.result


class GenerationBatcher:
    """Coalesce concurrent generate requests into batched scans.

    Sampling (temperature > 0) draws from a per-batch PRNG key advanced
    by an internal counter, so repeated requests get distinct samples.
    Per-request seeds are deliberately not exposed: one scan program
    shares a single key across its batch, so a request-level seed could
    not be honored once coalesced."""

    def __init__(self, engine: GenerationEngine,
                 flush_timeout_s: float = 0.01,
                 latency_window: int = 1024, registry=None):
        self.engine = engine
        self.flush_timeout_s = flush_timeout_s
        # obs.metrics registry: counters/latencies fold in as
        # serving/generate_* so they drain to run_telemetry.jsonl
        # (the /v2/stats JSON shape is unchanged)
        self.registry = registry
        self._queue: "queue.Queue[_PendingGen]" = queue.Queue()
        self._stop = threading.Event()
        self._latencies = deque(maxlen=latency_window)
        self._lat_lock = threading.Lock()
        self._carry: Optional[_PendingGen] = None
        self._carry_lock = threading.Lock()  # close() vs worker
        self._seed = 0  # per-batch: repeated sampled requests differ
        self.batches_run = 0
        self.requests_done = 0
        self._worker = threading.Thread(target=self._loop, daemon=True)
        self._worker.start()

    # -- client API -----------------------------------------------------
    def generate(self, prompt: Sequence[int], max_new_tokens: int = 16,
                 temperature: float = 0.0,
                 timeout: Optional[float] = 60.0) -> List[int]:
        return self.generate_async(
            prompt, max_new_tokens, temperature).wait(timeout)

    def generate_async(self, prompt, max_new_tokens: int = 16,
                       temperature: float = 0.0) -> _PendingGen:
        if self._stop.is_set():
            raise RuntimeError("GenerationBatcher is closed")
        # validate HERE so a bad request fails alone instead of
        # poisoning every request coalesced into its batch
        p = _PendingGen(prompt, max_new_tokens, temperature)
        if not 1 <= len(p.prompt) < self.engine.max_seq:
            raise ValueError(
                f"prompt length {len(p.prompt)} outside [1, "
                f"{self.engine.max_seq})")
        if p.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        self._queue.put(p)
        if self._stop.is_set():  # close() raced the put
            p.error = RuntimeError("GenerationBatcher is closed")
            p.event.set()
        return p

    @property
    def worker_alive(self) -> bool:
        """False once the worker thread has died (crash or close) —
        /v2/health reports "degraded" then, because every request
        submitted to a dead worker can only time out."""
        return self._worker.is_alive()

    def latency_stats(self) -> Dict[str, float]:
        from .batcher import latency_percentiles

        return latency_percentiles(self._latencies, self._lat_lock)

    def close(self):
        self._stop.set()
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline and self._worker.is_alive():
            self._drain()
            self._worker.join(timeout=0.2)
        self._drain()

    def _drain(self):
        with self._carry_lock:
            p, self._carry = self._carry, None
        if p is not None:
            p.error = RuntimeError("GenerationBatcher closed")
            p.event.set()
        while True:
            try:
                p = self._queue.get_nowait()
            except queue.Empty:
                return
            p.error = RuntimeError("GenerationBatcher closed")
            p.event.set()

    # -- worker ---------------------------------------------------------
    def _loop(self):
        while not self._stop.is_set():
            with self._carry_lock:
                first, self._carry = self._carry, None
            if first is None:
                try:
                    first = self._queue.get(timeout=0.05)
                except queue.Empty:
                    continue
            batch: List[_PendingGen] = [first]
            deadline = time.monotonic() + self.flush_timeout_s
            while len(batch) < self.engine.batch_size:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                try:
                    nxt = self._queue.get(timeout=remaining)
                except queue.Empty:
                    break
                if nxt.temperature != first.temperature:
                    # sampling temperature is baked into the compiled
                    # program: incompatible requests head the next batch
                    with self._carry_lock:
                        if self._stop.is_set():
                            nxt.error = RuntimeError(
                                "GenerationBatcher closed")
                            nxt.event.set()
                        else:
                            self._carry = nxt
                    break
                batch.append(nxt)
            self._run(batch)

    def _run(self, batch: List[_PendingGen]):
        try:
            self._seed += 1
            outs = self.engine.generate(
                [p.prompt for p in batch],
                [p.max_new_tokens for p in batch],
                temperature=batch[0].temperature,
                seed=self._seed,
            )
            now = time.monotonic()
            self.batches_run += 1
            for p, toks in zip(batch, outs):
                p.result = toks
                with self._lat_lock:
                    self._latencies.append(now - p.t_submit)
                self.requests_done += 1
                p.event.set()
            if self.registry is not None:
                reg = self.registry
                reg.counter("serving/generate_batches_run").inc()
                reg.counter("serving/generate_requests_done").inc(
                    len(batch))
                for p in batch:
                    reg.histogram(
                        "serving/generate_latency_ms").observe(
                        (now - p.t_submit) * 1e3)
        except Exception as e:
            for p in batch:
                p.error = e
                p.event.set()
