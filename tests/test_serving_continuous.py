"""Continuous-batching serving on a REAL trained GPT
(serving/scheduler.py + serving/kv_pool.py + the paged decode mode of
ops/attention.py): greedy token-identity against the static scan tier,
bit-identity of the paged decode step against the dense KV cache,
fault recovery through the donated-state reset path, and the Poisson
a mixed-length burst end to end."""
import numpy as np
import pytest
from _family import trained_gpt

from flexflow_tpu.decoding import build_paged_decode_step, make_decoder
from flexflow_tpu.serving import ContinuousScheduler, GenerationEngine

pytestmark = pytest.mark.slow  # search/train-heavy: full tier only

V, S, B = 32, 16, 4


@pytest.fixture(scope="module")
def trained(devices8):
    return trained_gpt(devices8, B, S, V)


def test_paged_decode_step_bit_identical_to_dense(trained, devices8):
    """The paged attention gather is shape-identical to the dense
    cache read, so logits match BIT FOR BIT at matching positions —
    the invariant everything else rides on."""
    import jax.numpy as jnp

    ff, ids = trained
    dense = make_decoder(ff, devices=devices8[:1])
    page = 4
    max_blocks = S // page
    paged = make_decoder(ff, devices=devices8[:1], kv_page_size=page,
                             kv_num_blocks=1 + B * max_blocks)
    step = build_paged_decode_step(paged)

    # non-contiguous physical blocks on purpose: row-major interleaved
    btab = np.zeros((B, max_blocks), np.int32)
    blocks = list(range(1, 1 + B * max_blocks))
    for j in range(max_blocks):
        for i in range(B):
            btab[i, j] = blocks.pop(0)
    state = paged._state
    for t in range(S - 1):
        toks = ids[:, t]
        slens = np.full(B, t, np.int32)
        logits, state = step(paged._weights, state,
                             jnp.asarray(toks), jnp.asarray(slens),
                             jnp.asarray(btab))
        want = np.asarray(dense.decode_step({
            "input": toks[:, None],
            "positions": np.full((B, 1), t, np.int32),
        }))[:, 0]
        np.testing.assert_array_equal(np.asarray(logits), want)


def test_continuous_token_identical_to_static_greedy(trained, devices8):
    """Acceptance criterion: continuous mode is token-identical to
    static mode for greedy decoding on the same prompts — mixed
    prompt lengths, mixed max_new_tokens, admissions interleaved with
    decode."""
    ff, _ = trained
    static = GenerationEngine(ff, batch_size=B, devices=devices8[:1])
    sched = ContinuousScheduler.from_trained(
        ff, batch_slots=B, page_size=4, devices=devices8[:1])
    try:
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, V, rng.randint(2, 8)).tolist()
                   for _ in range(12)]
        mnts = [int(rng.randint(2, 9)) for _ in range(12)]
        handles = [sched.generate_async(p, m)
                   for p, m in zip(prompts, mnts)]
        got = [h.wait(120.0) for h in handles]
        for p, m, g in zip(prompts, mnts, got):
            assert g == static.generate([p], m)[0]
        # 12 requests through 4 slots: iteration-level retirement
        # must have reused slots, and the pool must end empty
        assert sched.requests_done == 12
        sched.pool.check_invariants()
        assert sched.pool.used_blocks == 0
    finally:
        sched.close()


def test_continuous_eos_trimming_matches_static(trained, devices8):
    ff, ids = trained
    ref = GenerationEngine(ff, batch_size=B, devices=devices8[:1])
    want = ref.generate([ids[0, :4].tolist()], 8)[0]
    eos = int(want[6])  # force a hit inside the continuation
    static = GenerationEngine(ff, batch_size=B, devices=devices8[:1],
                              eos_id=eos)
    sched = ContinuousScheduler.from_trained(
        ff, batch_slots=B, page_size=4, devices=devices8[:1],
        eos_id=eos)
    try:
        p = ids[0, :4].tolist()
        got = sched.generate(p, 8, timeout=120.0)
        assert got == static.generate([p], 8)[0]
        assert got[-1] == eos and len(got) == 7
    finally:
        sched.close()


def test_real_fault_recovery_with_donated_state(trained, devices8):
    """A step exception mid-decode fails only the in-flight requests;
    the engine rebuilds its (donated) state and completes queued +
    subsequent requests correctly."""
    ff, _ = trained
    static = GenerationEngine(ff, batch_size=B, devices=devices8[:1])
    sched = ContinuousScheduler.from_trained(
        ff, batch_slots=B, page_size=4, devices=devices8[:1])
    real_step = sched.model.step
    calls = {"n": 0}

    def flaky_step(tokens, seq_lens, block_tables):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("injected mid-decode fault")
        return real_step(tokens, seq_lens, block_tables)

    sched.model.step = flaky_step
    try:
        hs = [sched.generate_async([1 + i, 2, 3], 6) for i in range(B)]
        failed = ok = 0
        for h in hs:
            try:
                h.wait(120.0)
                ok += 1
            except RuntimeError:
                failed += 1
        assert failed >= 1  # the in-flight batch died
        assert sched.step_failures == 1
        # post-fault request is still bit-correct vs static
        p = [5, 6, 7]
        assert sched.generate(p, 5, timeout=120.0) == \
            static.generate([p], 5)[0]
        sched.pool.check_invariants()
    finally:
        sched.close()


def test_chunked_prefill_writes_bit_identical_cache(trained, devices8):
    """The [slots, C] chunked-prefill program (a lax.scan of the seq-1
    decode graph) must write BIT-IDENTICAL K/V bytes to one-token
    prefill: after prefilling the same prompt both ways, the next
    decode step's logits match exactly."""
    import jax.numpy as jnp

    from flexflow_tpu.decoding import build_paged_prefill_step

    ff, ids = trained
    page, C = 4, 4
    max_blocks = S // page

    def fresh():
        paged = make_decoder(ff, devices=devices8[:1],
                                 kv_page_size=page,
                                 kv_num_blocks=1 + B * max_blocks)
        btab = np.zeros((B, max_blocks), np.int32)
        blocks = list(range(1, 1 + B * max_blocks))
        for j in range(max_blocks):
            for i in range(B):
                btab[i, j] = blocks.pop(0)
        return paged, btab

    plen = 9  # not chunk-aligned on purpose: the pad path is live
    # one-token prefill of positions 0..plen-2
    ref, btab = fresh()
    ref_step = build_paged_decode_step(ref)
    state = ref._state
    for t in range(plen - 1):
        _, state = ref_step(ref._weights, state,
                            jnp.asarray(ids[:, t]),
                            jnp.asarray(np.full(B, t, np.int32)),
                            jnp.asarray(btab))
    want, _ = ref_step(ref._weights, state,
                       jnp.asarray(ids[:, plen - 1]),
                       jnp.asarray(np.full(B, plen - 1, np.int32)),
                       jnp.asarray(btab))

    # chunked prefill of the same positions (2 chunks: 4 + 4)
    chk, btab2 = fresh()
    np.testing.assert_array_equal(btab, btab2)
    chk_prefill = build_paged_prefill_step(chk, C)
    chk_step = build_paged_decode_step(chk)
    state = chk._state
    for start in range(0, plen - 1, C):
        upto = min(start + C, plen - 1)
        tok = np.zeros((B, C), np.int32)
        tok[:, :upto - start] = ids[:, start:upto]
        state = chk_prefill(chk._weights, state, jnp.asarray(tok),
                            jnp.asarray(np.full(B, start, np.int32)),
                            jnp.asarray(btab))
    got, _ = chk_step(chk._weights, state,
                      jnp.asarray(ids[:, plen - 1]),
                      jnp.asarray(np.full(B, plen - 1, np.int32)),
                      jnp.asarray(btab))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_chunk_twin_multi_token_attention_matches(trained, devices8):
    """The true seq-C paged twin (make_decoder(step_tokens=C) +
    build_paged_chunk_step — the fused TPU-native prefill shape)
    agrees with one-token stepping to float tolerance (its batched
    matmuls are not rowwise-bitwise-stable on XLA:CPU, which is
    exactly why the engine's oracle path uses the scan program)."""
    import jax.numpy as jnp

    from flexflow_tpu.decoding import build_paged_chunk_step

    ff, ids = trained
    page, C = 4, 4
    max_blocks = S // page
    nb = 1 + B * max_blocks
    btab = np.zeros((B, max_blocks), np.int32)
    blocks = list(range(1, nb))
    for j in range(max_blocks):
        for i in range(B):
            btab[i, j] = blocks.pop(0)

    ref = make_decoder(ff, devices=devices8[:1], kv_page_size=page,
                           kv_num_blocks=nb)
    ref_step = build_paged_decode_step(ref)
    state = ref._state
    want = []
    for t in range(C):
        logits, state = ref_step(ref._weights, state,
                                 jnp.asarray(ids[:, t]),
                                 jnp.asarray(np.full(B, t, np.int32)),
                                 jnp.asarray(btab))
        want.append(np.asarray(logits))

    twin = make_decoder(ff, devices=devices8[:1], kv_page_size=page,
                            kv_num_blocks=nb, step_tokens=C)
    chunk_step = build_paged_chunk_step(twin)
    logits, _ = chunk_step(twin._weights, twin._state,
                           jnp.asarray(ids[:, :C]),
                           jnp.asarray(np.zeros(B, np.int32)),
                           jnp.asarray(btab))
    got = np.asarray(logits)  # [B, C, vocab]
    for t in range(C):
        np.testing.assert_allclose(got[:, t], want[t], rtol=2e-5,
                                   atol=2e-5)


def test_sharing_and_chunking_token_identical_to_baseline(trained,
                                                          devices8):
    """THE acceptance invariant: greedy output with prefix sharing +
    chunked prefill ON is token-identical to the PR 6 baseline
    (sharing OFF, one-token prefill) — including full-prompt hits
    (COW) and partial hits, with the pool invariants checked at every
    scheduler step."""
    ff, _ = trained
    base = ContinuousScheduler.from_trained(
        ff, batch_slots=B, page_size=4, devices=devices8[:1],
        prefix_cache=False, prefill_chunk=0)
    shared = ContinuousScheduler.from_trained(
        ff, batch_slots=B, page_size=4, devices=devices8[:1],
        prefix_cache=True, prefill_chunk=4, check_invariants=True)
    try:
        rng = np.random.RandomState(9)
        prefix = rng.randint(0, V, 8).tolist()  # 2 full pages
        prompts = [prefix]  # a FULL-prompt rehit once cached
        prompts += [prefix + rng.randint(0, V, rng.randint(1, 5)).tolist()
                    for _ in range(7)]
        prompts.append(prefix)  # full hit again, later in the stream
        mnts = [int(rng.randint(2, 7)) for _ in prompts]
        want = [base.generate(p, m, timeout=120.0)
                for p, m in zip(prompts, mnts)]
        handles = [shared.generate_async(p, m)
                   for p, m in zip(prompts, mnts)]
        got = [h.wait(120.0) for h in handles]
        assert got == want
        # sharing actually happened, and everything retired cleanly
        st = shared.stats()["prefix_cache"]
        assert st["hit_tokens"] > 0
        assert st["cow_copies"] >= 1  # the repeated full prompt
        shared.pool.check_invariants()
        assert shared.pool.used_blocks == 0
    finally:
        base.close()
        shared.close()


def test_chunk_pad_overflow_never_writes_real_blocks(trained, devices8):
    """Contract: a chunk whose trailing PAD positions run past the
    position table (a near-max_seq prompt's last chunk) must never
    write a real block — the prefill program routes them to scratch
    explicitly (and jax's current fill-mode gather would drop them
    anyway; the explicit guard keeps the contract independent of
    indexing-mode defaults, which differ between gather styles).
    Checked at the CACHE-BYTE level (not greedy tokens, which can
    survive a one-position corruption on a peaked model): after a
    chunk at pos 13 with pads at 14/15/16 (max_seq 16, page 4), every
    slot holding positions 0..13 must be byte-equal to the one-token
    reference."""
    import jax.numpy as jnp

    from flexflow_tpu.decoding import build_paged_prefill_step

    ff, ids = trained
    page, C = 4, 4
    max_blocks = S // page  # 4 columns: positions 0..15

    def fresh():
        paged = make_decoder(ff, devices=devices8[:1],
                                 kv_page_size=page,
                                 kv_num_blocks=1 + B * max_blocks)
        btab = np.arange(1, 1 + B * max_blocks,
                         dtype=np.int32).reshape(B, max_blocks)
        return paged, btab

    def decode_to(paged, btab, upto):
        step = build_paged_decode_step(paged)
        state = paged._state
        for t in range(upto):
            _, state = step(paged._weights, state,
                            jnp.asarray(ids[:, t]),
                            jnp.asarray(np.full(B, t, np.int32)),
                            jnp.asarray(btab))
        return state

    # reference: positions 0..13 written one token at a time
    ref, btab = fresh()
    ref_state = decode_to(ref, btab, 14)
    # under test: 0..12 one at a time, then ONE chunk at pos 13 —
    # real token at 13, pads at positions 14, 15, and 16 (= max_seq)
    chk, _ = fresh()
    state = decode_to(chk, btab, 13)
    prefill = build_paged_prefill_step(chk, C)
    tok = np.zeros((B, C), np.int32)
    tok[:, 0] = ids[:, 13]
    state = prefill(chk._weights, state, jnp.asarray(tok),
                    jnp.asarray(np.full(B, 13, np.int32)),
                    jnp.asarray(btab))
    for op in ref_state:
        for k in ("k_cache", "v_cache"):
            if k not in ref_state[op]:
                continue
            want = np.asarray(ref_state[op][k])
            got = np.asarray(state[op][k])
            for i in range(B):
                for col in range(max_blocks):
                    blk = btab[i, col]
                    for off in range(page):
                        if col * page + off > 13:
                            continue  # pads 14/15 may hold garbage
                        np.testing.assert_array_equal(
                            got[blk, off], want[blk, off],
                            err_msg=f"{op}.{k} row {i} position "
                                    f"{col * page + off} corrupted "
                                    "by a pad write")


def test_cow_divergence_bit_identical_to_independent(trained, devices8):
    """Two requests sharing a full-prompt prefix then DIVERGING
    (different sampling seeds) must each match a fully-independent
    run bit for bit — the COW copies isolate their tails."""
    ff, _ = trained
    rng = np.random.RandomState(4)
    prompt = rng.randint(0, V, 8).tolist()  # exactly 2 pages

    def run_pair(prefix_cache):
        sched = ContinuousScheduler.from_trained(
            ff, batch_slots=B, page_size=4, devices=devices8[:1],
            prefix_cache=prefix_cache, check_invariants=prefix_cache,
            seed=123)
        try:
            warm = sched.generate(prompt, 2, timeout=120.0)
            # submitted together: both full-prompt hits when sharing,
            # diverging immediately via per-request sampling seeds
            h1 = sched.generate_async(prompt, 6, temperature=0.8)
            h2 = sched.generate_async(prompt, 6, temperature=0.8)
            r1, r2 = h1.wait(120.0), h2.wait(120.0)
            if prefix_cache:
                assert h1.prefix_hit_tokens == 8
                assert h2.prefix_hit_tokens == 8
            sched.pool.check_invariants()
            return warm, r1, r2
        finally:
            sched.close()

    shared = run_pair(True)
    independent = run_pair(False)
    assert shared == independent
    assert shared[1] != shared[2]  # the seeds genuinely diverged


def test_mixed_length_burst_end_to_end_continuous(trained, devices8):
    ff, _ = trained
    sched = ContinuousScheduler.from_trained(
        ff, batch_slots=B, page_size=4, devices=devices8[:1])
    try:
        sched.generate([1, 2], 2, timeout=120.0)  # pay the compile
        rng = np.random.RandomState(5)
        wl = [(rng.randint(0, V, int(rng.randint(2, 7))).tolist(),
               int(rng.randint(8, 11) if i % 3 == 0
                   else rng.randint(2, 7))) for i in range(10)]
        handles = [sched.generate_async(p, m) for p, m in wl]
        outs = [h.wait(120.0) for h in handles]
        assert [len(o) - len(p) for o, (p, _) in zip(outs, wl)] == \
            [m for _, m in wl]
        assert all(o[:len(p)] == p for o, (p, _) in zip(outs, wl))
        assert all(h.t_first_token is not None for h in handles)
        st = sched.stats()
        assert st["ttft"]["n"] == 11
        assert st["kv_pool"]["peak_used_blocks"] > 0
        assert st["kv_pool"]["used_blocks"] == 0
    finally:
        sched.close()
