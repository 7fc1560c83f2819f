"""Continuous-batching scheduler logic (serving/scheduler.py) against
a deterministic fake step model — admission/retirement interleaving,
fault isolation (in-flight fails, queued survives), close-drain, SLO
telemetry, and the serve_http satellites (timeout_s -> 503, degraded
health, continuous /v2/stats) — all without compiling a real model."""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from flexflow_tpu.obs.metrics import MetricsRegistry
from flexflow_tpu.serving import ContinuousScheduler, KVPool
from flexflow_tpu.serving.server import serve_http

V = 16


class FakeStepModel:
    """Pure-host stand-in for PagedKVDecodeModel: the next token is
    always (input token + 1) % vocab, delivered as one-hot logits, so
    greedy expectations are computable in closed form.  Optional
    per-step delay (close-drain tests) and scripted failures.
    prefill_chunk/prefix_cache mirror the real model's knobs — the
    fake has no device cache, so prefill_step/copy_block just record
    calls (scheduler logic is what's under test here)."""

    def __init__(self, batch_slots=2, max_seq=32, page_size=4,
                 num_blocks=None, delay_s=0.0, prefill_chunk=0,
                 prefix_cache=True):
        self.batch_slots = batch_slots
        self.max_seq = max_seq
        self.page_size = page_size
        self.max_blocks_per_seq = max_seq // page_size
        self.num_blocks = (num_blocks if num_blocks is not None
                           else 1 + batch_slots * self.max_blocks_per_seq)
        self.vocab = V
        self.delay_s = delay_s
        self.prefill_chunk = prefill_chunk
        self.prefix_cache = prefix_cache
        self.steps = 0
        self.prefill_calls = 0
        self.copied_blocks = []
        self.fail_at_steps = set()
        self.resets = 0

    def reset(self):
        self.resets += 1

    def step(self, tokens, seq_lens, block_tables):
        self.steps += 1
        if self.steps in self.fail_at_steps:
            raise RuntimeError(f"injected step fault @{self.steps}")
        if self.delay_s:
            time.sleep(self.delay_s)
        logits = np.zeros((self.batch_slots, V), np.float32)
        nxt = (np.asarray(tokens) + 1) % V
        logits[np.arange(self.batch_slots), nxt] = 1.0
        return logits

    def prefill_step(self, tokens, positions, block_tables):
        self.prefill_calls += 1
        if self.delay_s:
            time.sleep(self.delay_s)

    def copy_block(self, src, dst):
        self.copied_blocks.append((src, dst))


def expected(prompt, mnt):
    out = list(prompt)
    t = prompt[-1]
    for _ in range(mnt):
        t = (t + 1) % V
        out.append(t)
    return out


def test_greedy_matches_closed_form_and_interleaves():
    sched = ContinuousScheduler(FakeStepModel(batch_slots=2))
    try:
        reqs = [([1, 2, 3], 4), ([5], 9), ([7, 8], 2), ([2, 4, 6, 8], 5),
                ([11], 3)]
        handles = [sched.generate_async(p, m) for p, m in reqs]
        for h, (p, m) in zip(handles, reqs):
            assert h.wait(30.0) == expected(p, m)
        assert sched.requests_done == len(reqs)
        # 5 requests through 2 slots: retirement freed slots mid-run
        assert sched.batches_run < sum(len(p) + m for p, m in reqs)
        st = sched.stats()
        assert st["kv_pool"]["used_blocks"] == 0  # all retired
        assert st["ttft"]["n"] == len(reqs)
        sched.pool.check_invariants()
    finally:
        sched.close()


def test_mixed_temperatures_share_one_batch():
    """Static batching must segregate temperatures (one compiled scan
    per temperature); continuous samples host-side per row and takes
    any mix."""
    sched = ContinuousScheduler(FakeStepModel(batch_slots=2))
    try:
        h1 = sched.generate_async([3, 4], 5, temperature=0.0)
        h2 = sched.generate_async([5, 6], 5, temperature=1.0)
        r1, r2 = h1.wait(30.0), h2.wait(30.0)
        assert r1 == expected([3, 4], 5)
        assert len(r2) == 7 and all(0 <= t < V for t in r2)
    finally:
        sched.close()


def test_small_pool_queues_admissions():
    # pool fits ONE 8-token sequence (2 usable blocks of 4); the
    # second request queues until the first retires — never crashes
    model = FakeStepModel(batch_slots=2, num_blocks=3)
    reg = MetricsRegistry()
    sched = ContinuousScheduler(model, registry=reg)
    try:
        h1 = sched.generate_async([1, 2, 3], 5)  # 8 tokens: whole pool
        h2 = sched.generate_async([4, 5], 4)
        assert h1.wait(30.0) == expected([1, 2, 3], 5)
        assert h2.wait(30.0) == expected([4, 5], 4)
        assert reg.counter("serving/admissions_deferred").value > 0
        sched.pool.check_invariants()
    finally:
        sched.close()


def test_unservable_request_fails_alone():
    model = FakeStepModel(batch_slots=2, num_blocks=2)  # 1 usable block
    sched = ContinuousScheduler(model)
    try:
        h = sched.generate_async([1, 2, 3], 8)  # needs 3 blocks
        with pytest.raises(ValueError, match="KV blocks"):
            h.wait(30.0)
        # the engine still serves what fits
        assert sched.generate([1], 2, timeout=30.0) == expected([1], 2)
    finally:
        sched.close()


def test_step_fault_fails_inflight_only_queued_survive():
    """ISSUE 6 satellite: an injected step exception mid-decode fails
    only the affected in-flight requests; queued requests survive and
    complete after the engine recovers."""
    model = FakeStepModel(batch_slots=2)
    model.fail_at_steps = {3}
    sched = ContinuousScheduler(model)
    try:
        # 2 admitted immediately (slots=2), 2 queued behind them
        inflight = [sched.generate_async([1, 2], 6),
                    sched.generate_async([3, 4], 6)]
        queued = [sched.generate_async([5, 6], 3),
                  sched.generate_async([7, 8], 4)]
        for h in inflight:
            with pytest.raises(RuntimeError, match="injected step fault"):
                h.wait(30.0)
        assert queued[0].wait(30.0) == expected([5, 6], 3)
        assert queued[1].wait(30.0) == expected([7, 8], 4)
        assert sched.step_failures == 1
        assert model.resets == 1  # donated-state rebuild ran
        assert sched.requests_done == 2
        sched.pool.check_invariants()
        assert sched.pool.used_blocks == 0
    finally:
        sched.close()


def test_close_during_inflight_drains_without_hanging():
    """ISSUE 6 satellite: close() during an in-flight continuous batch
    fails the waiters promptly instead of letting them sit out their
    full timeouts."""
    model = FakeStepModel(batch_slots=2, delay_s=0.05)
    sched = ContinuousScheduler(model)
    hs = [sched.generate_async([1, 2], 30) for _ in range(4)]
    time.sleep(0.1)  # let a batch get in flight
    t0 = time.monotonic()
    sched.close()
    assert time.monotonic() - t0 < 30.0
    for h in hs:
        with pytest.raises(RuntimeError, match="closed"):
            h.wait(5.0)
    assert not sched.worker_alive
    with pytest.raises(RuntimeError, match="closed"):
        sched.generate_async([1], 1)


def test_close_drains_even_when_step_is_wedged():
    """A device step that never returns must not park waiters for
    their full timeouts: close() force-drains after its deadline even
    though the worker thread is still stuck in model.step."""
    model = FakeStepModel(batch_slots=2, delay_s=10.0)  # "wedged"
    sched = ContinuousScheduler(model, close_timeout_s=0.5)
    h = sched.generate_async([1, 2], 20)
    time.sleep(0.2)  # let the worker enter the wedged step
    t0 = time.monotonic()
    sched.close()
    assert time.monotonic() - t0 < 5.0
    with pytest.raises(RuntimeError, match="closed"):
        h.wait(1.0)  # failed by the force-drain, not a timeout


def test_slo_metrics_drain_to_registry(tmp_path):
    reg = MetricsRegistry()
    sched = ContinuousScheduler(FakeStepModel(batch_slots=2),
                                registry=reg)
    try:
        sched.generate([1, 2], 5, timeout=30.0)
        sched.generate([3], 8, timeout=30.0)
    finally:
        sched.close()
    path = tmp_path / "run_telemetry.jsonl"
    assert reg.write_jsonl(str(path)) > 0
    recs = [json.loads(ln) for ln in path.read_text().splitlines()]
    by_name = {r["name"]: r for r in recs if "name" in r}
    assert by_name["serving/requests_done"]["value"] == 2
    assert by_name["serving/ttft_ms"]["count"] == 2
    assert by_name["serving/steps"]["value"] == sched.batches_run
    assert by_name["serving/kv_occupancy"]["count"] > 0
    assert by_name["serving/kv_fragmentation"]["count"] > 0
    # the summary tool renders the new rows
    import importlib
    summary = importlib.import_module("tools.telemetry_summary")
    text = summary.summarize(recs)
    assert "Serving" in text and "ttft_ms" in text


def test_mixed_length_burst_completes_with_slo_stamps():
    """Eight requests of mixed prompt and reply lengths, a quarter of
    them long, all submitted before any finishes: each completes at its
    own length and its handle carries the stamps TTFT and per-token
    latency are computed from."""
    sched = ContinuousScheduler(FakeStepModel(batch_slots=2))
    try:
        rng = np.random.RandomState(0)
        wl = [(rng.randint(0, V, int(rng.randint(1, 5))).tolist(),
               int(rng.randint(10, 15) if i % 4 == 0
                   else rng.randint(2, 7))) for i in range(8)]
        handles = [sched.generate_async(p, m) for p, m in wl]
        for h, (p, m) in zip(handles, wl):
            assert h.wait(30.0) == expected(p, m)
            assert h.n_generated == m
            assert h.t_submit <= h.t_first_token <= h.t_done
        assert sched.requests_done == 8
        st = sched.stats()
        assert st["ttft"]["n"] == 8
        assert st["tokens_generated"] == sum(m for _, m in wl)
    finally:
        sched.close()


# -- prefix cache + chunked prefill (scheduler logic, fake model) -------

def test_chunked_prefill_cuts_prompt_steps():
    """A long prompt through chunked prefill costs ~plen/C prefill
    dispatches plus the decode steps — and the closed-form greedy
    output is unchanged (the chunk program is acceleration, never
    semantics)."""
    model = FakeStepModel(batch_slots=2, prefill_chunk=4)
    sched = ContinuousScheduler(model, check_invariants=True)
    try:
        prompt = [(3 * i + 1) % V for i in range(20)]
        assert sched.generate(prompt, 4, timeout=30.0) == \
            expected(prompt, 4)
        assert model.prefill_calls > 0
        # unchunked would pay ~19 prefill steps; chunked pays ~19/4
        # chunk dispatches (plus the decode steps that ride along)
        assert sched.prefill_steps <= 6
        st = sched.stats()
        assert st["prefill_chunk"] == 4
        assert st["prefill_steps"] == sched.prefill_steps
    finally:
        sched.close()


def test_prefix_hit_skips_prefill_and_stamps_handle():
    model = FakeStepModel(batch_slots=2)
    reg = MetricsRegistry()
    sched = ContinuousScheduler(model, registry=reg,
                                check_invariants=True)
    try:
        prompt = list(range(1, 13))  # 12 tokens = 3 full pages of 4
        h1 = sched.generate_async(prompt + [13, 14], 3)
        assert h1.wait(30.0) == expected(prompt + [13, 14], 3)
        assert h1.prefix_hit_tokens == 0
        steps_cold = model.steps
        # same 12-token prefix, different tail: the cached blocks are
        # mapped at admission and those positions never prefill
        h2 = sched.generate_async(prompt + [20, 21], 3)
        assert h2.wait(30.0) == expected(prompt + [20, 21], 3)
        assert h2.prefix_hit_tokens == 12
        assert model.steps - steps_cold < steps_cold
        assert reg.counter("serving/prefix_hit_tokens").value >= 12
        st = sched.stats()["prefix_cache"]
        assert st["hits"] >= 1 and st["hit_tokens"] >= 12
        sched.pool.check_invariants()
    finally:
        sched.close()


def test_shared_prefix_burst_stamps_hit_tokens_on_every_handle():
    """Ten requests over two shared 8-token heads (two full pages of 4)
    with unique tails, submitted together: every handle carries
    `prefix_hit_tokens`, whole pages only, and the heads are re-hit."""
    sched = ContinuousScheduler(FakeStepModel(batch_slots=2, max_seq=64),
                                check_invariants=True)
    try:
        rng = np.random.RandomState(3)
        heads = [rng.randint(0, V, 8).tolist() for _ in range(2)]
        reqs = [(heads[i % 2]
                 + rng.randint(0, V, int(rng.randint(1, 4))).tolist(),
                 int(rng.randint(2, 5))) for i in range(10)]
        handles = [sched.generate_async(p, m) for p, m in reqs]
        for h, (p, m) in zip(handles, reqs):
            assert h.wait(30.0) == expected(p, m)
        hits = [h.prefix_hit_tokens for h in handles]
        assert all(hit in (0, 4, 8) for hit in hits), hits
        assert sum(hits) > 0
        assert sched.stats()["prefix_cache"]["hit_tokens"] == sum(hits)
    finally:
        sched.close()


def test_full_prompt_hit_cows_and_matches_closed_form():
    """An identical repeated prompt is a FULL-prompt hit: only the
    last prompt token re-runs (for its logits), the shared tail block
    is copy-on-written first, and the output is byte-equal."""
    model = FakeStepModel(batch_slots=2)
    sched = ContinuousScheduler(model, check_invariants=True)
    try:
        prompt = list(range(1, 9))  # exactly 2 pages
        first = sched.generate(prompt, 5, timeout=30.0)
        steps_cold = model.steps
        again = sched.generate(prompt, 5, timeout=30.0)
        assert again == first == expected(prompt, 5)
        assert model.copied_blocks, "full hit must trigger COW"
        # replay cost: 1 re-run token + 5 decode steps, not 8 + 5
        assert model.steps - steps_cold <= 7
        sched.pool.check_invariants()
    finally:
        sched.close()


def test_sharing_and_chunking_compose_with_faults():
    """The PR 6 fault discipline survives the new machinery: a step
    fault mid-decode fails in-flight only, the reset invalidates the
    prefix index (cached bytes were zeroed), and later same-prefix
    requests still complete correctly (re-prefilled, then re-cached)."""
    model = FakeStepModel(batch_slots=2, prefill_chunk=4)
    model.fail_at_steps = {2}
    sched = ContinuousScheduler(model, check_invariants=True)
    try:
        prompt = list(range(1, 13))
        h1 = sched.generate_async(prompt, 6)
        with pytest.raises(RuntimeError, match="injected step fault"):
            h1.wait(30.0)
        assert model.resets == 1
        assert sched.pool.cached_blocks == 0  # index invalidated
        assert sched.generate(prompt, 3, timeout=30.0) == \
            expected(prompt, 3)
        # and the re-run re-populated the cache for the NEXT hit
        h3 = sched.generate_async(prompt + [20], 3)
        assert h3.wait(30.0) == expected(prompt + [20], 3)
        assert h3.prefix_hit_tokens > 0
        sched.pool.check_invariants()
    finally:
        sched.close()


def test_prefix_cache_off_never_shares():
    model = FakeStepModel(batch_slots=2, prefix_cache=False)
    sched = ContinuousScheduler(model, check_invariants=True)
    try:
        prompt = list(range(1, 9))
        assert sched.generate(prompt, 3, timeout=30.0) == \
            expected(prompt, 3)
        h = sched.generate_async(prompt, 3)
        assert h.wait(30.0) == expected(prompt, 3)
        assert h.prefix_hit_tokens == 0
        assert sched.stats()["prefix_cache"]["hits"] == 0
    finally:
        sched.close()


def test_prefix_metrics_and_summary_render(tmp_path):
    reg = MetricsRegistry()
    model = FakeStepModel(batch_slots=2, prefill_chunk=4)
    sched = ContinuousScheduler(model, registry=reg)
    try:
        prompt = list(range(1, 13))
        sched.generate(prompt, 3, timeout=30.0)
        sched.generate(prompt + [20, 21], 3, timeout=30.0)
    finally:
        sched.close()
    path = tmp_path / "run_telemetry.jsonl"
    assert reg.write_jsonl(str(path)) > 0
    recs = [json.loads(ln) for ln in path.read_text().splitlines()]
    by_name = {r["name"]: r for r in recs if "name" in r}
    assert by_name["serving/prefix_hit_tokens"]["value"] >= 12
    assert "serving/kv_shared_blocks" in by_name
    import importlib
    summary = importlib.import_module("tools.telemetry_summary")
    text = summary.summarize(recs)
    assert "prefix" in text  # the Serving section's prefix-cache rows


# -- serve_http satellites ----------------------------------------------

def _post(port, payload, path="/v2/generate"):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.status, json.loads(r.read())


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return json.loads(r.read())


def test_http_generate_timeout_maps_to_503():
    """ISSUE 6 satellite: /v2/generate honors request-supplied
    timeout_s and maps TimeoutError to 503 (not a generic 400) — the
    request keeps decoding server-side."""
    model = FakeStepModel(batch_slots=2, delay_s=0.05)
    sched = ContinuousScheduler(model)
    server = serve_http(generator=sched, port=0, block=False)
    port = server.server_address[1]
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, {"prompt": [1, 2], "max_new_tokens": 25,
                         "timeout_s": 0.05})
        assert ei.value.code == 503
        body = json.loads(ei.value.read())
        assert "TimeoutError" in body["error"] and body["retriable"]
        # a sane timeout still succeeds (and bad timeouts are 400s)
        status, out = _post(port, {"prompt": [1, 2], "max_new_tokens": 2,
                                   "timeout_s": 20.0})
        assert status == 200
        assert out["tokens"] == [expected([1, 2], 2)]
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, {"prompt": [1], "timeout_s": -1})
        assert ei.value.code == 400
    finally:
        server.shutdown()
        sched.close()


def test_http_engine_fault_maps_to_500_not_400():
    """A server-side engine fault (here: a closed batcher) is the
    server's problem — 500 retriable, not a 400 client error."""
    sched = ContinuousScheduler(FakeStepModel(batch_slots=2))
    server = serve_http(generator=sched, port=0, block=False)
    port = server.server_address[1]
    try:
        sched.close()
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, {"prompt": [1, 2], "max_new_tokens": 2})
        assert ei.value.code == 500
        assert json.loads(ei.value.read())["retriable"]
        # malformed requests still map to 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, {"max_new_tokens": 2})  # no prompt at all
        assert ei.value.code == 400
    finally:
        server.shutdown()
        sched.close()


def test_http_health_degrades_on_dead_worker():
    """ISSUE 6 satellite: a dead worker thread must flip /v2/health to
    "degraded" instead of leaving it green while requests time out."""
    sched = ContinuousScheduler(FakeStepModel(batch_slots=2))
    server = serve_http(generator=sched, port=0, block=False)
    port = server.server_address[1]
    try:
        sched.generate([1], 2, timeout=30.0)
        assert _get(port, "/v2/health")["status"] == "ok"
        stats = _get(port, "/v2/stats")
        # legacy shape unchanged...
        assert {"batches_run", "requests_done", "latency"} <= set(stats)
        # ...plus the continuous block
        cont = stats["continuous"]
        assert cont["mode"] == "continuous"
        assert "kv_pool" in cont and "ttft" in cont
        sched.close()  # worker thread exits
        # degraded rides a 503 so status-code-only probes see it too
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(port, "/v2/health")
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["status"] == "degraded"
    finally:
        server.shutdown()
        sched.close()
