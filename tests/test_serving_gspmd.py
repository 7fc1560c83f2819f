"""GSPMD tensor-parallel paged serving (docs/SERVING.md
"Tensor-parallel replicas"): one replica spans tp chips on a
{"data": 1, "model": tp} mesh — attention heads, FFN channels and the
paged KV block pools' head dims shard over the model axis, so per-chip
KV bytes are 1/tp while the host-owned block-table machinery (prefix
sharing, COW, chunked prefill) is untouched.  The acceptance bar is
greedy TOKEN-IDENTITY against the single-chip gather oracle at every
tp degree, with the pool invariant checker armed at every scheduler
step, plus NamedSharding inspection of the per-chip pool bytes and
fault recovery through the sharding-preserving reset path."""
import pytest
from _family import trained_gpt

from flexflow_tpu.decoding import make_decoder
from flexflow_tpu.serving import ContinuousScheduler

pytestmark = pytest.mark.slow  # compile-heavy: full tier only

V, S, B = 32, 16, 4

# shared-prefix workload: three requests share a full-page prefix (the
# COW + prefix-cache machinery engages), one is cold
PREFIX = [3, 5, 7, 2]
PROMPTS = [PREFIX + [9, 4], PREFIX + [9, 11], PREFIX + [1], [8, 2]]
MNT = [6, 6, 5, 4]


@pytest.fixture(scope="module")
def trained(devices8):
    return trained_gpt(devices8, B, S, V)[0]


def make_sched(ff, devices8, tp, **kw):
    kw.setdefault("batch_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_blocks", 12)
    kw.setdefault("check_invariants", True)  # pool audited every step
    return ContinuousScheduler.from_trained(
        ff, devices=devices8[:max(tp, 1)], tp=tp, **kw)


def run_workload(sched):
    try:
        return [sched.generate(p, m, timeout=240.0)
                for p, m in zip(PROMPTS, MNT)]
    finally:
        sched.close()


@pytest.fixture(scope="module")
def oracle(trained, devices8):
    """Single-chip gather formulation: the bit-identity reference every
    tp degree must reproduce token for token."""
    return run_workload(make_sched(trained, devices8, tp=1))


def test_tp_greedy_token_identity_vs_single_chip_oracle(
        trained, devices8, oracle):
    """tp in {2, 4}: head-sharded pools + GSPMD-partitioned decode
    step, gather formulation — greedy completions token-identical to
    the tp=1 oracle on the shared-prefix workload."""
    for tp in (2, 4):
        got = run_workload(make_sched(trained, devices8, tp=tp))
        assert got == oracle, f"tp={tp} diverged from the oracle"


def test_tp_pallas_chunked_prefill_token_identity(
        trained, devices8, oracle):
    """The full acceptance combo at tp=2: prefix sharing + chunked
    prefill + the Pallas paged kernel (shard_map over the head axis),
    still token-identical to the single-chip gather oracle."""
    sched = make_sched(trained, devices8, tp=2, paged_kernel="pallas",
                       prefill_chunk=2)
    stats = None
    try:
        got = [sched.generate(p, m, timeout=240.0)
               for p, m in zip(PROMPTS, MNT)]
        stats = sched.stats()
    finally:
        sched.close()
    assert got == oracle
    # the sharing machinery actually engaged on the sharded pool
    assert stats["prefix_cache"]["hits"] > 0
    assert stats["paged_kernel"]["formulation"] == "pallas"
    assert stats["tp"]["degree"] == 2


def test_pool_sharded_over_heads_per_chip_bytes(trained, devices8):
    """NamedSharding inspection: every layer's K/V block pool is
    [num_blocks, page, h, d] sharded P(None, None, 'model') over a
    2-chip mesh, so each chip holds exactly 1/2 of the pool bytes —
    the headline per-chip KV claim, checked on the actual buffers."""
    from jax.sharding import NamedSharding, PartitionSpec

    sched = make_sched(trained, devices8, tp=2)
    try:
        model = sched.model
        pools = [(name, entries[k])
                 for name, entries in model._state.items()
                 if name.startswith("attn_")
                 for k in ("k_cache", "v_cache")]
        assert len(pools) == 4  # 2 layers x k/v
        for name, pool in pools:
            sh = pool.sharding
            assert isinstance(sh, NamedSharding), (name, sh)
            assert len(sh.device_set) == 2
            assert sh.spec == PartitionSpec(None, None, "model"), name
            for shard in pool.addressable_shards:
                assert shard.data.nbytes * 2 == pool.nbytes
        # the telemetry agrees with the buffers
        tp_block = sched.stats()["tp"]
        assert tp_block["kv_block_bytes_per_chip"] * 2 == \
            tp_block["kv_block_bytes"]
        per_chip_pool = sum(p.nbytes for _, p in pools) // 2
        assert tp_block["kv_pool_bytes_per_chip"] == per_chip_pool
    finally:
        sched.close()


def test_prefix_cache_cow_parity_on_sharded_pool(trained, devices8,
                                                 oracle):
    """Prefix sharing and copy-on-write address only the UNSHARDED
    block/page axes, so they work unchanged on head-sharded physical
    blocks: shared-prefix requests hit the cache, diverge through COW
    copies, and stay token-identical."""
    sched = make_sched(trained, devices8, tp=2)
    stats = None
    try:
        got = [sched.generate(p, m, timeout=240.0)
               for p, m in zip(PROMPTS, MNT)]
        stats = sched.stats()
        sched.pool.check_invariants()
    finally:
        sched.close()
    assert got == oracle
    pc = stats["prefix_cache"]
    assert pc["hits"] > 0 and pc["hit_tokens"] >= len(PREFIX)


def test_fault_recovery_reset_preserves_sharding(trained, devices8,
                                                 oracle):
    """A mid-decode fault on the tp=2 engine: the donated-state reset
    rebuilds ZEROED pools that keep their NamedSharding (a bare
    jnp.zeros would silently gather them onto one chip), and post-fault
    requests are still token-identical to the oracle."""
    from jax.sharding import NamedSharding

    sched = make_sched(trained, devices8, tp=2)
    real_step = sched.model.step
    calls = {"n": 0}

    def flaky_step(tokens, seq_lens, block_tables):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("injected mid-decode fault")
        return real_step(tokens, seq_lens, block_tables)

    sched.model.step = flaky_step
    try:
        hs = [sched.generate_async(p, m)
              for p, m in zip(PROMPTS, MNT)]
        failed = ok = 0
        for h in hs:
            try:
                h.wait(240.0)
                ok += 1
            except RuntimeError:
                failed += 1
        assert failed >= 1  # the in-flight batch died
        # the reset state still spans both chips
        for name, entries in sched.model._state.items():
            for k, v in entries.items():
                assert isinstance(v.sharding, NamedSharding), (name, k)
                assert len(v.sharding.device_set) == 2, (name, k)
        # post-fault decode is still token-identical to the oracle
        for (p, m), want in zip(zip(PROMPTS, MNT), oracle):
            assert sched.generate(p, m, timeout=240.0) == want
        sched.pool.check_invariants()
    finally:
        sched.close()


def test_tp_strategy_served_through_store(trained, devices8, tmp_path):
    """The searched tp decode strategy is keyed by the decode graph x
    the replica mesh fingerprint: a second replica at the same tp
    restores from the store, a different tp degree gets its own key."""
    store = tmp_path / "store"
    old = trained.config.strategy_store
    trained.config.strategy_store = str(store)
    try:
        d1 = make_decoder(trained, batch_size=2, kv_page_size=4,
                              kv_num_blocks=12, tp=2,
                              devices=devices8[:2])
        assert d1.strategy.search_stats["store_hit"] is False
        d2 = make_decoder(trained, batch_size=2, kv_page_size=4,
                              kv_num_blocks=12, tp=2,
                              devices=devices8[:2])
        assert d2.strategy.search_stats["store_hit"] is True
        assert d2.strategy.search_stats["store_key"] == \
            d1.strategy.search_stats["store_key"]
        # a different mesh degree is a different key — no false hit
        d4 = make_decoder(trained, batch_size=2, kv_page_size=4,
                              kv_num_blocks=12, tp=4,
                              devices=devices8[:4])
        assert d4.strategy.search_stats["store_hit"] is False
        assert d4.strategy.search_stats["store_key"] != \
            d1.strategy.search_stats["store_key"]
    finally:
        trained.config.strategy_store = old
