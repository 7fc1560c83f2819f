"""Replicated serving front (serving/front.py + serving/replica.py):
queue handoff on replica death, supervised restarts under the
resilience primitives (FaultPlan / StepWatchdog / RetryPolicy),
bounded per-request requeues, load shedding with Retry-After, and the
/v2/health ok|degraded|down aggregation — all against the
deterministic fake step model (no compiles)."""
import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from flexflow_tpu.obs.metrics import MetricsRegistry
from flexflow_tpu.resilience.faults import Fault, FaultKind, FaultPlan
from flexflow_tpu.serving import ServiceUnavailable, ServingFront
from flexflow_tpu.serving.server import serve_http

V = 16
NO_SLEEP = lambda s: None  # noqa: E731


class FakeStepModel:
    """Deterministic stand-in for PagedKVDecodeModel: next token is
    (input + 1) % vocab as one-hot logits, so greedy expectations are
    closed-form — which makes requeue-after-death TOKEN-IDENTITY
    directly checkable.  Optional per-step delay simulates a hung
    device dispatch for the watchdog."""

    def __init__(self, batch_slots=2, max_seq=32, page_size=4,
                 delay_s=0.0):
        self.batch_slots = batch_slots
        self.max_seq = max_seq
        self.page_size = page_size
        self.max_blocks_per_seq = max_seq // page_size
        self.num_blocks = 1 + batch_slots * self.max_blocks_per_seq
        self.vocab = V
        self.delay_s = delay_s
        self.steps = 0

    def reset(self):
        pass

    def step(self, tokens, seq_lens, block_tables):
        self.steps += 1
        if self.delay_s:
            time.sleep(self.delay_s)
        logits = np.zeros((self.batch_slots, V), np.float32)
        nxt = (np.asarray(tokens) + 1) % V
        logits[np.arange(self.batch_slots), nxt] = 1.0
        return logits


def expected(prompt, mnt):
    out = list(prompt)
    t = prompt[-1]
    for _ in range(mnt):
        t = (t + 1) % V
        out.append(t)
    return out


def factory(replica_id, survivors=None):
    return FakeStepModel()


def kill_on_steps(steps, kind=FaultKind.HUNG_STEP):
    return FaultPlan([Fault(step=s, kind=kind) for s in steps])


def _wait_for(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return False


# -- basic replicated serving -------------------------------------------

def test_front_serves_across_replicas():
    front = ServingFront(factory, num_replicas=2, sleep=NO_SLEEP)
    try:
        reqs = [([1, 2, 3], 4), ([5], 9), ([7, 8], 2), ([2, 4, 6, 8], 5),
                ([11], 3), ([3], 6)]
        hs = [front.generate_async(p, m) for p, m in reqs]
        for h, (p, m) in zip(hs, reqs):
            assert h.wait(30.0) == expected(p, m)
        assert front.requests_done == len(reqs)
        assert front.health()["status"] == "ok"
        st = front.stats()
        assert st["mode"] == "replicated"
        assert len(st["replicas"]) == 2
        # the dispatcher spread work: both replicas stepped
        assert all(r["batches_run"] > 0 for r in st["replicas"])
    finally:
        front.close()


def test_front_stamps_its_backlog_at_admission_on_every_handle():
    """`queue_depth_at_admit` is the front's backlog as the request
    came in: 0 for the first of a burst into one two-slot replica, and
    a later request of the same burst has seen a deeper one (a step
    takes 10 ms here, the twelve submissions far less)."""
    front = ServingFront(
        lambda rid, survivors=None: FakeStepModel(delay_s=0.01),
        num_replicas=1, sleep=NO_SLEEP)
    try:
        rng = np.random.RandomState(0)
        reqs = [(rng.randint(0, V, int(rng.randint(2, 7))).tolist(),
                 int(rng.randint(2, 7))) for _ in range(12)]
        hs = [front.generate_async(p, m) for p, m in reqs]
        for h, (p, m) in zip(hs, reqs):
            assert h.wait(30.0) == expected(p, m)
        depths = [h.queue_depth_at_admit for h in hs]
        assert all(isinstance(d, int) and d >= 0 for d in depths)
        assert depths[0] == 0 and max(depths) > 0
    finally:
        front.close()


def test_a_completions_slot_is_refilled_by_the_next_step():
    """With a backlog at the front, the slot a completion frees holds
    the backlog's head in the very next step: the hand-over is made on
    the decode loop's own thread, inside the completion, and does not
    wait for the dispatcher to wake (it would race the scheduler's
    next admission, and the slot would stand empty for a step in some
    runs).  So between the first full step and the last, every step
    has both rows live."""
    live = []

    class Counting(FakeStepModel):
        def step(self, tokens, seq_lens, block_tables):
            live.append(int((np.asarray(block_tables)[:, 0] != 0).sum()))
            return super().step(tokens, seq_lens, block_tables)

    front = ServingFront(lambda rid, survivors=None: Counting(delay_s=0.003),
                         num_replicas=1, sleep=NO_SLEEP)
    try:
        rng = np.random.RandomState(3)
        reqs = [([int(rng.randint(1, V))], int(rng.randint(2, 6)))
                for _ in range(40)]
        hs = [front.generate_async(p, m) for p, m in reqs]
        for h, (p, m) in zip(hs, reqs):
            assert h.wait(30.0) == expected(p, m)
    finally:
        front.close()
    first = live.index(2)
    last = len(live) - 1 - live[::-1].index(2)
    assert last - first > 40
    assert set(live[first:last + 1]) == {2}


def test_front_validates_at_admission():
    front = ServingFront(factory, num_replicas=1, sleep=NO_SLEEP)
    try:
        with pytest.raises(ValueError, match="prompt length"):
            front.generate_async([], 4)
        with pytest.raises(ValueError, match="max_new_tokens"):
            front.generate_async([1], 0)
    finally:
        front.close()


# -- replica death: requeue + token identity ----------------------------

def test_replica_death_requeues_inflight_token_identical():
    """ISSUE 8: injected replica death mid-stream — in-flight requests
    are requeued and complete TOKEN-IDENTICALLY (greedy) on a
    surviving replica; queued requests are untouched; the dead replica
    restarts under supervision."""
    reg = MetricsRegistry()
    front = ServingFront(
        factory, num_replicas=2, registry=reg, sleep=NO_SLEEP,
        retry_backoff=0.0,
        fault_plans={0: kill_on_steps([2])},
    )
    try:
        # more requests than both replicas' slots: some queue at front
        reqs = [([1 + i, 2], 8) for i in range(6)]
        hs = [front.generate_async(p, m) for p, m in reqs]
        for h, (p, m) in zip(hs, reqs):
            assert h.wait(30.0) == expected(p, m)  # fault-free tokens
        assert front.requeued_requests >= 1
        assert reg.counter("serving/replica_deaths").value == 1
        assert front.replicas[0].deaths == 1
        assert _wait_for(lambda: front.replicas[0].state == "live")
        assert reg.counter("serving/replica_restarts").value == 1
        assert front.health()["status"] == "ok"
        # the front never returned a non-retriable error for an
        # admitted request
        assert front.requests_done == len(reqs)
    finally:
        front.close()


def test_device_loss_rebuilds_on_survivors():
    """A DeviceLossFault carries the surviving device count into the
    replica's rebuild factory (the degraded-mesh path)."""
    seen = []

    def recording_factory(replica_id, survivors=None):
        seen.append((replica_id, survivors))
        return FakeStepModel()

    plan = FaultPlan.single(1, FaultKind.DEVICE_LOSS, survivors=4)
    front = ServingFront(recording_factory, num_replicas=1,
                         sleep=NO_SLEEP, retry_backoff=0.0,
                         fault_plans={0: plan})
    try:
        assert front.generate([1, 2], 5, timeout=30.0) == \
            expected([1, 2], 5)
        assert _wait_for(lambda: front.replicas[0].restarts == 1)
        assert seen[0] == (0, None)
        assert seen[1] == (0, 4)  # rebuilt on the surviving count
    finally:
        front.close()


def test_hung_decode_step_routes_through_watchdog():
    """A REAL hang (step blocks past serving_step_timeout) raises
    HungStepTimeout via the StepWatchdog, kills the engine, and the
    supervisor restarts it — requests complete on the restarted
    replica instead of waiting forever."""
    built = []

    def hang_once_factory(replica_id, survivors=None):
        m = FakeStepModel(delay_s=5.0 if not built else 0.0)
        built.append(m)
        return m

    front = ServingFront(hang_once_factory, num_replicas=1,
                         step_timeout=0.3, sleep=NO_SLEEP,
                         retry_backoff=0.0)
    try:
        h = front.generate_async([1, 2], 4)
        assert h.wait(30.0) == expected([1, 2], 4)
        assert front.replicas[0].deaths == 1
        assert front.replicas[0].restarts == 1
        from flexflow_tpu.resilience.watchdog import HungStepTimeout

        assert isinstance(front.replicas[0].last_error, HungStepTimeout)
        assert front.requeued_requests == 1
    finally:
        front.close()


# -- shedding and budgets -----------------------------------------------

def test_all_replicas_down_sheds_with_retry_after():
    reg = MetricsRegistry()
    front = ServingFront(
        factory, num_replicas=2, registry=reg, sleep=NO_SLEEP,
        retry_backoff=0.0, max_restarts=0, request_retry_limit=5,
        fault_plans={0: kill_on_steps(range(50)),
                     1: kill_on_steps(range(50))},
    )
    try:
        h = front.generate_async([1, 2], 4)  # drives both to death
        with pytest.raises(ServiceUnavailable):
            h.wait(30.0)
        assert _wait_for(
            lambda: front.health()["status"] == "down")
        assert all(r["state"] == "dead"
                   for r in front.health()["replicas"])
        with pytest.raises(ServiceUnavailable) as ei:
            front.generate_async([1], 2)
        assert ei.value.retry_after_s > 0
        assert front.shed_requests == 1
        assert reg.counter("serving/shed_requests").value == 1
    finally:
        front.close()


def test_restart_budget_exhaustion_marks_replica_dead():
    """One poisoned replica exhausts its budget and goes PERMANENTLY
    dead; the front keeps serving on the survivor and reports
    degraded."""
    front = ServingFront(
        factory, num_replicas=2, sleep=NO_SLEEP, retry_backoff=0.0,
        max_restarts=1, request_retry_limit=5,
        fault_plans={0: kill_on_steps(range(100))},
    )
    try:
        for i in range(6):
            # the poisoned replica is routed to again only once its
            # rebuild is over: six requests can all finish on the
            # survivor while it is still "restarting"
            assert _wait_for(
                lambda: front.replicas[0].state != "restarting")
            assert front.generate([1 + i], 4, timeout=30.0) == \
                expected([1 + i], 4)
        assert _wait_for(lambda: front.replicas[0].state == "dead")
        health = front.health()
        assert health["status"] == "degraded"
        assert health["replicas"][0]["state"] == "dead"
        # still serving on the survivor
        assert front.generate([9], 3, timeout=30.0) == expected([9], 3)
    finally:
        front.close()


def test_request_retry_limit_exhaustion_is_retriable():
    """A request that keeps landing on dying replicas fails with a
    RETRIABLE ServiceUnavailable after request_retry_limit requeues —
    never a client error."""
    front = ServingFront(
        factory, num_replicas=1, sleep=NO_SLEEP, retry_backoff=0.0,
        max_restarts=100, request_retry_limit=2,
        fault_plans={0: kill_on_steps(range(200))},
    )
    try:
        h = front.generate_async([1, 2], 6)
        with pytest.raises(ServiceUnavailable, match="3 times"):
            h.wait(30.0)
        assert h.retries == 3  # initial + 2 requeues, all consumed
        assert front.requeued_requests == 2
    finally:
        front.close()


# -- shutdown -----------------------------------------------------------

def test_front_close_bounded_with_wedged_replica():
    """A replica wedged inside a decode step (no watchdog armed)
    cannot hang front shutdown: every close is bounded."""

    def wedged_factory(replica_id, survivors=None):
        return FakeStepModel(delay_s=30.0)

    front = ServingFront(wedged_factory, num_replicas=2,
                         sleep=NO_SLEEP, close_timeout_s=0.5)
    h = front.generate_async([1, 2], 4)
    time.sleep(0.2)  # let a step wedge
    t0 = time.monotonic()
    front.close()
    assert time.monotonic() - t0 < 10.0
    with pytest.raises(RuntimeError):
        h.wait(1.0)
    with pytest.raises(RuntimeError, match="closed"):
        front.generate_async([1], 1)


# -- metrics ------------------------------------------------------------

def test_front_metrics_and_summary(tmp_path):
    reg = MetricsRegistry()
    front = ServingFront(
        factory, num_replicas=2, registry=reg, sleep=NO_SLEEP,
        retry_backoff=0.0, fault_plans={0: kill_on_steps([2])},
    )
    try:
        hs = [front.generate_async([1 + i], 6) for i in range(4)]
        for h in hs:
            h.wait(30.0)
        # the first request goes to replica 0, so its death on its third
        # step is certain; its restart, on the supervisor's thread, is
        # not over when the requeued requests finish elsewhere.  Wait
        # for it on the front's own condition (every replica state
        # change notifies it) before closing.
        with front._cv:
            assert front._cv.wait_for(
                lambda: front.replicas[0].restarts >= 1, timeout=30.0)
        front.stats()  # refreshes the replicas_live gauge
    finally:
        front.close()
    names = {m for m in reg._metrics}
    assert "serving/replica_deaths" in names
    assert "serving/replica_restarts" in names
    assert "serving/requeued_requests" in names
    assert "serving/replica/0/queue_depth" in names
    assert "serving/replica/1/queue_depth" in names
    assert "serving/replicas_live" in names
    path = tmp_path / "run_telemetry.jsonl"
    assert reg.write_jsonl(str(path)) > 0
    recs = [json.loads(ln) for ln in path.read_text().splitlines()]
    import importlib

    summary = importlib.import_module("tools.telemetry_summary")
    text = summary.summarize(recs)
    assert "replica_deaths" in text and "requeued_requests" in text


# -- HTTP surface -------------------------------------------------------

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


def test_http_front_health_stats_and_shed():
    front = ServingFront(
        factory, num_replicas=2, sleep=NO_SLEEP, retry_backoff=0.0,
        max_restarts=0, request_retry_limit=3,
        fault_plans={0: kill_on_steps(range(50)),
                     1: kill_on_steps(range(50))},
    )
    server = serve_http(generator=front, port=0, block=False)
    port = server.server_address[1]
    try:
        health = _get(port, "/v2/health")
        assert health["status"] == "ok"
        assert [r["state"] for r in health["replicas"]] == ["live"] * 2
        # the first request drives both replicas to permanent death
        # (every step is a kill; max_restarts=0): its retries exhaust
        # into a 503 retriable with a Retry-After
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, {"prompt": [1, 2], "max_new_tokens": 3})
        assert ei.value.code == 503
        assert ei.value.headers.get("Retry-After") is not None
        assert json.loads(ei.value.read())["retriable"]
        assert _wait_for(lambda: front.health()["status"] == "down")
        # down rides a 503 for status-code-only probes
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(port, "/v2/health")
        assert ei.value.code == 503
        body = json.loads(ei.value.read())
        assert body["status"] == "down"
        # shed new requests: 503 + Retry-After
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, {"prompt": [1], "max_new_tokens": 2})
        assert ei.value.code == 503
        assert ei.value.headers.get("Retry-After") is not None
        assert json.loads(ei.value.read())["retriable"]
        # stats carries the per-replica block
        stats = _get(port, "/v2/stats")
        reps = stats["continuous"]["replicas"]
        assert [r["state"] for r in reps] == ["dead", "dead"]
    finally:
        server.shutdown()
        front.close()


def test_http_front_serves_and_degrades():
    front = ServingFront(
        factory, num_replicas=2, sleep=NO_SLEEP, retry_backoff=0.0,
        max_restarts=0, request_retry_limit=3,
        fault_plans={0: kill_on_steps(range(50))},  # replica 0 dies
    )
    server = serve_http(generator=front, port=0, block=False)
    port = server.server_address[1]
    try:
        status, out = _post(port, {"prompts": [[1, 2], [5]],
                                   "max_new_tokens": 4})
        assert status == 200
        assert out["tokens"] == [expected([1, 2], 4), expected([5], 4)]
        assert _wait_for(lambda: front.replicas[0].state == "dead")
        # degraded still SERVES, so it rides a 200 (unlike the
        # single-engine degraded, which cannot serve at all)
        health = _get(port, "/v2/health")
        assert health["status"] == "degraded"
        status, out = _post(port, {"prompt": [3], "max_new_tokens": 2})
        assert status == 200 and out["tokens"] == [expected([3], 2)]
    finally:
        server.shutdown()
        front.close()


# -- tensor-parallel replicas: chip budget + cache-affine dispatch ------

def _tp_factory(tp):
    """FakeStepModel dressed with the tensor-parallel surface a
    PagedKVDecodeModel exposes (tp / mesh_shape / per-chip KV bytes)."""
    def f(replica_id, survivors=None):
        m = FakeStepModel()
        m.tp = tp
        m.mesh_shape = {"data": 1, "model": tp}
        m.kv_block_bytes = 1024
        m.kv_block_bytes_per_chip = 1024 // tp
        return m
    return f


def test_front_chip_budget_refuses_add_replica():
    """Fleet chips = replicas x tp; an add_replica that would exceed
    --serving-chip-budget is refused BEFORE any compile and counted."""
    reg = MetricsRegistry()
    front = ServingFront(_tp_factory(2), num_replicas=1, chip_budget=4,
                         registry=reg, sleep=NO_SLEEP)
    try:
        assert front.chips_per_replica == 2
        front.add_replica()  # 4 chips: fits exactly
        with pytest.raises(RuntimeError, match="chip budget exhausted"):
            front.add_replica()
        assert reg.counter("serving/chip_budget_refused").value == 1
        st = front.stats()
        assert st["chips_per_replica"] == 2
        assert st["chip_budget"] == 4
        assert st["fleet_chips"] == 4
        # the per-replica tp block rides /v2/stats
        tp = st["replicas"][0]["tp"]
        assert tp["degree"] == 2
        assert tp["mesh_shape"] == {"data": 1, "model": 2}
        assert tp["kv_block_bytes_per_chip"] * 2 == tp["kv_block_bytes"]
    finally:
        front.close()


def test_front_chip_budget_validates_initial_fleet():
    with pytest.raises(ValueError, match="chip budget"):
        ServingFront(_tp_factory(4), num_replicas=2, chip_budget=4,
                     sleep=NO_SLEEP)


def test_front_without_budget_keeps_prior_behavior():
    front = ServingFront(_tp_factory(2), num_replicas=1, sleep=NO_SLEEP)
    try:
        for _ in range(3):
            front.add_replica()  # unbounded: no refusal
        assert len(front.replicas) == 4
        assert front.stats()["chip_budget"] == 0
    finally:
        front.close()


def test_dispatch_is_cache_affine():
    """The dispatcher routes a request to the replica whose prefix
    cache holds the longest prefix of its prompt — not least-loaded —
    and falls back to least-loaded for cold prompts."""
    reg = MetricsRegistry()
    front = ServingFront(factory, num_replicas=2, registry=reg,
                         sleep=NO_SLEEP)
    try:
        r0, r1 = front.replicas
        # pretend replica 1 (NOT first in rotation) holds the blocks
        r1.scheduler.cached_prefix_tokens = (
            lambda p: 4 if list(p)[:4] == [1, 2, 3, 4] else 0)
        r0.scheduler.cached_prefix_tokens = lambda p: 0
        h = front.generate_async([1, 2, 3, 4, 5], 3)
        assert h.wait(30.0) == expected([1, 2, 3, 4, 5], 3)
        assert r1.stats()["batches_run"] > 0
        assert r0.stats()["batches_run"] == 0
        assert reg.counter("serving/cache_affine_routed").value == 1
        # a cold prompt falls back to least-loaded (replica 0 first)
        h = front.generate_async([9, 9], 2)
        assert h.wait(30.0) == expected([9, 9], 2)
        assert r0.stats()["batches_run"] > 0
    finally:
        front.close()


def test_cache_affinity_follows_real_prefix_cache():
    """End to end on the real block pool: the first shared-prefix
    request warms ONE replica's prefix cache; every later request with
    the same prefix routes to that same replica (its prefill becomes a
    block-table metadata hit), leaving the other replica cold."""
    front = ServingFront(factory, num_replicas=2, sleep=NO_SLEEP)
    try:
        prefix = [1, 2, 3, 4]  # one full page (page_size=4)
        assert front.generate(prefix + [5], 3) == \
            expected(prefix + [5], 3)
        warm = [r for r in front.replicas
                if r.stats()["batches_run"] > 0]
        assert len(warm) == 1
        for tail in ([6], [7, 8], [5]):
            assert front.generate(prefix + tail, 3) == \
                expected(prefix + tail, 3)
        cold = [r for r in front.replicas if r is not warm[0]]
        assert cold[0].stats()["batches_run"] == 0
        assert warm[0].scheduler.cached_prefix_tokens(prefix + [5]) >= 4
    finally:
        front.close()
