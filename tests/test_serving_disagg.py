"""Disaggregated prefill/decode serving fleet (serving/disagg.py,
docs/SERVING.md "Disaggregated fleet"): --serving-roles parsing, the
migrate-vs-re-prefill cost model, the DisaggServingFront dispatcher's
divert/migrate/requeue pipeline on a deterministic fake KV model —
both cost decisions reachable, completions token-identical to the
colocated fleet — and the transfer fault matrix (BLOB_PARTIAL_UPLOAD /
BLOB_TRANSIENT / BLOB_UNAVAILABLE through a FaultyBlobStore fabric):
every mid-stream fault degrades to a re-prefill that still yields the
exact tokens, never corrupt output.  The slow section reruns the token
-identity oracle through real trained engines on both paged-attention
kernels with the pool invariant checker armed."""
import numpy as np
import pytest
from _family import engine_factory, trained_gpt

from flexflow_tpu.obs.metrics import MetricsRegistry
from flexflow_tpu.resilience.faults import Fault, FaultKind, FaultPlan
from flexflow_tpu.serving import (
    BlobStoreFabric, DisaggServingFront, InProcessFabric,
    MigrationCostModel, ServingFront, parse_serving_roles)
from flexflow_tpu.store.blobstore import FaultyBlobStore, LocalBlobStore

V = 16
NO_SLEEP = lambda s: None  # noqa: E731


# -- role spec parsing ---------------------------------------------------

def test_parse_roles_counts_and_bare_names():
    assert parse_serving_roles("prefill=1,decode=2") == \
        ["prefill", "decode", "decode"]
    assert parse_serving_roles("prefill,decode") == ["prefill", "decode"]
    assert parse_serving_roles("mixed=2") == ["mixed", "mixed"]
    assert parse_serving_roles("") is None
    assert parse_serving_roles(None) is None
    assert parse_serving_roles("prefill=0,decode=1") == ["decode"]


def test_parse_roles_rejects_bad_specs():
    with pytest.raises(ValueError, match="bad count"):
        parse_serving_roles("prefill=x")
    with pytest.raises(ValueError, match="unknown role"):
        parse_serving_roles("verify=1")
    with pytest.raises(ValueError, match="must be >= 0"):
        parse_serving_roles("decode=-1")
    with pytest.raises(ValueError, match="empty spec"):
        parse_serving_roles(" , ")
    with pytest.raises(ValueError, match="decode-capable"):
        parse_serving_roles("prefill=2")
    with pytest.raises(ValueError, match="names 3"):
        parse_serving_roles("prefill=1,decode=2", num_replicas=2)


def test_front_rejects_decode_free_and_missized_roles():
    factory = lambda rid, survivors=None: FakeKVModel()  # noqa: E731
    with pytest.raises(ValueError, match="decode-capable"):
        ServingFront(factory, 2, roles=["prefill", "prefill"],
                     sleep=NO_SLEEP)
    with pytest.raises(ValueError, match="every replica"):
        ServingFront(factory, 2, roles=["mixed"], sleep=NO_SLEEP)
    with pytest.raises(ValueError, match="unknown replica role"):
        ServingFront(factory, 1, roles=["verify"], sleep=NO_SLEEP)


# -- cost model ----------------------------------------------------------

def test_cost_model_subpage_prompt_always_reprefills():
    m = MigrationCostModel()
    d = m.decide(prompt_len=3, new_blocks=0, page_size=4,
                 block_bytes=1 << 20, chunk=0, step_s=5e-3)
    assert d["decision"] == "reprefill" and d["new_blocks"] == 0


def test_cost_model_cheap_hop_migrates():
    m = MigrationCostModel(fabric_kind="inproc")
    d = m.decide(prompt_len=8, new_blocks=2, page_size=4,
                 block_bytes=4096, chunk=0, step_s=5e-3)
    # 2 blocks over ICI ~ microseconds vs 8 decode steps ~ 40ms
    assert d["decision"] == "migrate"
    assert d["migrate_s"] < d["reprefill_s"]


def test_cost_model_expensive_stream_reprefills():
    # a giant KV payload over DCN costs more than recomputing it
    m = MigrationCostModel(fabric_kind="blob")
    d = m.decide(prompt_len=8, new_blocks=2, page_size=4,
                 block_bytes=10 << 30, chunk=0, step_s=5e-3)
    assert d["decision"] == "reprefill"
    assert d["migrate_s"] > d["reprefill_s"]


def test_cost_model_cap_scales_the_threshold():
    # same workload: a generous cap admits the migration a strict
    # cap refuses
    kw = dict(prompt_len=8, new_blocks=2, page_size=4,
              block_bytes=45 << 20, chunk=0, step_s=5e-3)
    lax = MigrationCostModel(cost_cap=20.0, fabric_kind="blob")
    strict = MigrationCostModel(cost_cap=0.01, fabric_kind="blob")
    assert lax.decide(**kw)["decision"] == "migrate"
    assert strict.decide(**kw)["decision"] == "reprefill"


def test_cost_model_tail_tokens_price_into_migrate():
    m = MigrationCostModel()
    aligned = m.decide(prompt_len=8, new_blocks=2, page_size=4,
                       block_bytes=0, chunk=0, step_s=5e-3)
    tailed = m.decide(prompt_len=10, new_blocks=2, page_size=4,
                      block_bytes=0, chunk=0, step_s=5e-3)
    # the 2-token sub-page tail still re-prefills on the adopter
    assert tailed["migrate_s"] > aligned["migrate_s"]


def test_cost_model_rejects_bad_cap():
    with pytest.raises(ValueError, match="cost cap"):
        MigrationCostModel(cost_cap=0)


# -- fake-model fleet ----------------------------------------------------

class FakeKVModel:
    """Deterministic next-token model with an exportable KV surface:
    token t emits t+1 mod V, so completions have a closed form and any
    corruption shows up as wrong tokens."""

    def __init__(self, batch_slots=2, max_seq=32, page_size=4):
        self.batch_slots = batch_slots
        self.max_seq = max_seq
        self.page_size = page_size
        self.max_blocks_per_seq = max_seq // page_size
        self.num_blocks = 1 + batch_slots * self.max_blocks_per_seq
        self.vocab = V
        self.steps = 0
        self.kv = np.zeros((self.num_blocks, page_size, 2), np.float32)

    def reset(self):
        pass

    def step(self, tokens, seq_lens, block_tables):
        self.steps += 1
        logits = np.zeros((self.batch_slots, V), np.float32)
        nxt = (np.asarray(tokens) + 1) % V
        logits[np.arange(self.batch_slots), nxt] = 1.0
        return logits

    def export_block(self, block):
        return {"kv": np.array(self.kv[block])}

    def import_block(self, block, arrays):
        self.kv[block] = arrays["kv"]


def expected(prompt, mnt):
    out = list(prompt)
    t = prompt[-1]
    for _ in range(mnt):
        t = (t + 1) % V
        out.append(t)
    return out


def factory(rid, survivors=None):
    return FakeKVModel()


# multi-page prompts migrate (fake kv_block_bytes=0 prices the stream
# at ~one hop latency); the sub-page prompt has new_blocks=0 so it
# always re-prefills — both dispatcher decisions are deterministic
REQS = [([1, 2, 3, 4, 5, 6, 7, 8], 4), ([5], 3),
        ([1, 2, 3, 4, 5, 6, 7, 8], 4), ([9, 10, 11, 12], 5)]


def run_fleet(front, reqs=REQS, timeout=30.0):
    hs = [front.generate_async(p, m) for p, m in reqs]
    outs = [h.wait(timeout) for h in hs]
    return hs, outs


def test_disagg_fleet_token_identity_and_both_decisions():
    reg = MetricsRegistry()
    front = DisaggServingFront(factory, num_replicas=2,
                               roles=["prefill", "decode"],
                               registry=reg, sleep=NO_SLEEP)
    try:
        hs, outs = run_fleet(front)
        st = front.stats()
        h = front.health()
    finally:
        front.close()
    for (p, m), got in zip(REQS, outs):
        assert got == expected(p, m)
    assert st["mode"] == "disaggregated"
    dg = st["disagg"]
    assert dg["migrate_decisions"] > 0
    assert dg["reprefill_decisions"] > 0  # the [5] sub-page prompt
    assert dg["migrations_ok"] > 0 and dg["migrations_failed"] == 0
    assert dg["kv_transfer"]["fabric"] == "inproc"
    assert dg["kv_transfer"]["bytes_streamed"] > 0
    assert reg.counter("serving/disagg_migrate_decisions").value == \
        dg["migrate_decisions"]
    assert reg.counter("serving/kv_migration_done").value == \
        dg["migrations_ok"]
    # prefill replicas never serve client decodes
    assert all(h_.served_role == "decode" for h_ in hs)
    # per-class fleet accounting in stats + health
    assert set(st["roles"]) == {"prefill", "decode"}
    assert st["roles"]["decode"]["live"] == 1
    assert h["status"] == "ok" and set(h["roles"]) == \
        {"prefill", "decode"}


def test_disagg_migration_is_a_prefix_cache_hit():
    front = DisaggServingFront(factory, num_replicas=2,
                               roles=["prefill", "decode"],
                               sleep=NO_SLEEP)
    try:
        prompt = [1, 2, 3, 4, 5, 6, 7, 8]
        h = front.generate_async(prompt, 4)
        assert h.wait(30.0) == expected(prompt, 4)
        rec = h.migration
    finally:
        front.close()
    assert rec is not None and rec["decision"] == "migrate"
    assert rec["ok"] is True
    # the adopted blocks made the re-dispatched prompt a cache hit
    # (capped at plen-1 page-aligned: the last token still computes)
    assert h.prefix_hit_tokens >= ((len(prompt) - 1) // 4) * 4


def test_disagg_migrates_at_most_once_per_request():
    """The one-migration guard: a request whose migration already ran
    (ok or not) dispatches normally on requeue instead of ping-ponging
    through the prefill class forever."""
    class DeadFabric(InProcessFabric):
        def transfer(self, key, data):
            raise RuntimeError("fabric down")

    reg = MetricsRegistry()
    front = DisaggServingFront(factory, num_replicas=2,
                               roles=["prefill", "decode"],
                               fabric=DeadFabric(),
                               registry=reg, sleep=NO_SLEEP)
    try:
        prompt = [1, 2, 3, 4, 5, 6, 7, 8]
        h = front.generate_async(prompt, 4)
        got = h.wait(30.0)
        st = front.stats()
    finally:
        front.close()
    assert got == expected(prompt, 4)  # re-prefill, correct tokens
    assert h.migration["decision"] == "migrate"
    assert h.migration["ok"] is False
    assert st["disagg"]["migrations_failed"] == 1
    assert st["disagg"]["migrate_decisions"] == 1  # no second divert
    assert reg.counter("serving/kv_migration_failed").value == 1


def test_mixed_fleet_stays_colocated():
    """No prefill class -> the divert hook never fires and the front
    behaves exactly like the base ServingFront."""
    front = DisaggServingFront(factory, num_replicas=2,
                               roles=["mixed", "mixed"], sleep=NO_SLEEP)
    try:
        hs, outs = run_fleet(front)
        st = front.stats()
    finally:
        front.close()
    for (p, m), got in zip(REQS, outs):
        assert got == expected(p, m)
    assert st["disagg"]["migrate_decisions"] == 0
    assert all(h.migration is None for h in hs)


def test_colocated_front_oracle_token_identity():
    """The acceptance oracle at fake-model scale: greedy completions
    through the disagg fleet byte-identical to the colocated front."""
    colo = ServingFront(factory, 2, sleep=NO_SLEEP)
    try:
        _, want = run_fleet(colo)
    finally:
        colo.close()
    disagg = DisaggServingFront(factory, num_replicas=2,
                                roles=["prefill", "decode"],
                                sleep=NO_SLEEP)
    try:
        _, got = run_fleet(disagg)
        assert disagg.stats()["disagg"]["migrate_decisions"] > 0
    finally:
        disagg.close()
    assert got == want


# -- transfer fault matrix -----------------------------------------------

def faulty_blob_fabric(tmp_path, *faults):
    store = FaultyBlobStore(LocalBlobStore(str(tmp_path)),
                            FaultPlan(list(faults)), sleep=NO_SLEEP)
    return BlobStoreFabric(store), store


@pytest.mark.parametrize("kind,expect_failed", [
    (FaultKind.BLOB_PARTIAL_UPLOAD, True),   # torn object LANDS; only
                                             # the reader crc catches it
    (FaultKind.BLOB_TRANSIENT, True),        # put/get raises once
    (FaultKind.BLOB_UNAVAILABLE, True),      # outage window
    (FaultKind.BLOB_LATENCY, False),         # slow but correct
])
def test_fault_matrix_degrades_to_reprefill_token_identical(
        tmp_path, kind, expect_failed):
    fab, store = faulty_blob_fabric(
        tmp_path, Fault(step=1, kind=kind))
    reg = MetricsRegistry()
    # a huge cost cap keeps the decision "migrate" despite DCN pricing,
    # so the fault actually lands on the streaming path
    front = DisaggServingFront(factory, num_replicas=2,
                               roles=["prefill", "decode"],
                               fabric=fab, migration_cost_cap=1e9,
                               registry=reg, sleep=NO_SLEEP)
    try:
        hs, outs = run_fleet(front)
        st = front.stats()
    finally:
        front.close()
    # the acceptance bar: a mid-stream fault NEVER produces wrong
    # tokens — worst case is a re-prefill of the same prompt
    for (p, m), got in zip(REQS, outs):
        assert got == expected(p, m)
    assert st["disagg"]["migrate_decisions"] > 0
    if expect_failed:
        assert st["disagg"]["migrations_failed"] >= 1
        assert reg.counter("serving/kv_migration_failed").value >= 1
    else:
        assert st["disagg"]["migrations_failed"] == 0
        assert st["disagg"]["migrations_ok"] > 0


def test_fault_matrix_counters_match_store_injections(tmp_path):
    fab, store = faulty_blob_fabric(
        tmp_path,
        Fault(step=1, kind=FaultKind.BLOB_PARTIAL_UPLOAD,
              payload={"fraction": 0.5}))
    front = DisaggServingFront(factory, num_replicas=2,
                               roles=["prefill", "decode"],
                               fabric=fab, migration_cost_cap=1e9,
                               sleep=NO_SLEEP)
    try:
        prompt = [1, 2, 3, 4, 5, 6, 7, 8]
        h = front.generate_async(prompt, 4)
        got = h.wait(30.0)
        st = front.stats()
    finally:
        front.close()
    assert got == expected(prompt, 4)
    assert store.counters["partial_uploads"] == 1
    assert h.migration["ok"] is False
    assert st["disagg"]["kv_transfer"]["fabric"] == "blob"


# -- real engines (full tier) --------------------------------------------

V_GPT, S_GPT, B_GPT = 32, 16, 4
PREFIX = [3, 5, 7, 2]
PROMPTS = [PREFIX + [9, 4], PREFIX + [9, 11], PREFIX + [1], [8, 2]]
MNT = [6, 6, 5, 4]


@pytest.fixture(scope="module")
def trained(devices8):
    return trained_gpt(devices8, B_GPT, S_GPT, V_GPT)[0]



def run_real(front):
    try:
        hs = [front.generate_async(p, m)
              for p, m in zip(PROMPTS, MNT)]
        return [h.wait(240.0) for h in hs], front.stats()
    finally:
        front.close()


@pytest.mark.slow
@pytest.mark.parametrize("kernel", ["gather", "pallas"])
def test_disagg_token_identity_vs_colocated_engine(
        trained, devices8, kernel):
    """The PR's acceptance oracle on real engines: greedy completions
    through a 1-prefill + 1-decode disagg fleet byte-identical to the
    colocated 2-mixed front, on BOTH paged-attention formulations,
    with the pool invariant checker armed at every scheduler step and
    at least one migration actually streamed."""
    factory = engine_factory(trained, kernel, devices8[:1])
    colo = ServingFront(factory, 2, check_invariants=True)
    want, _ = run_real(colo)

    disagg = DisaggServingFront(
        factory, 2, roles=["prefill", "decode"], check_invariants=True)
    got, st = run_real(disagg)

    assert got == want
    assert st["disagg"]["migrate_decisions"] > 0
    assert st["disagg"]["migrations_ok"] > 0
    assert st["disagg"]["kv_transfer"]["blocks_streamed"] > 0


def test_telemetry_summary_renders_disagg_line(tmp_path):
    import importlib
    import json

    reg = MetricsRegistry()
    front = DisaggServingFront(factory, num_replicas=2,
                               roles=["prefill", "decode"],
                               registry=reg, sleep=NO_SLEEP)
    try:
        front.generate_async([1, 2, 3, 4, 5, 6, 7, 8], 4).wait(30.0)
        front.generate_async([5], 3).wait(30.0)
    finally:
        front.close()
    path = tmp_path / "run_telemetry.jsonl"
    assert reg.write_jsonl(str(path)) > 0
    recs = [json.loads(ln) for ln in path.read_text().splitlines()]
    summary = importlib.import_module("tools.telemetry_summary")
    text = summary.summarize(recs)
    assert "disaggregated fleet" in text
    assert "migrate=1" in text and "reprefill=1" in text
    assert "migrations_done=1" in text
