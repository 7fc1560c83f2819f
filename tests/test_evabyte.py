"""The `evabyte` family behind the serving front, at a toy size on the
CPU in float32 (window 16, chunk 4, 2 layers, 2 heads;
`benchmarks/configs/toy-evabyte.json`): chunked prefill then decode
THROUGH THE STATE (a window that fills and starts again, a store that
grows a row a chunk) against the reference's full forward, on logits;
a slot's second tenant; rows of one dispatch in different windows; the
dispatch counters against arithmetic from the lengths; admission by
what a sequence holds; what the family does not carry, by name.

The reference (`benchmarks/families/evabyte.py`) shares no code with
`flexflow_tpu/ops/eva_attention.py`.  LOGIT_TOL is 2e-5 of the largest
logit: both sides are float32 sums of a few hundred terms in other
orders (the op's softmax runs over the window, the step and the store
side by side, the reference's over a window and the store).
"""
import jax
import numpy as np
import pytest

from _family import Recorder, close, config
from benchmarks.families import evabyte as fam
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.config import ConfigError
from flexflow_tpu.models.evabyte import build_evabyte
from flexflow_tpu.ops.eva_attention import eva_row_counts

CFG = config("toy-evabyte.json")
D = fam.dims(CFG)
SEED = 11
LOGIT_TOL = 2e-5
SLOTS = 3


def holder(cfg=CFG, **ffconfig):
    """The served model's holder with the seed's weights set."""
    ffconfig.setdefault("prefix_cache", False)
    ff = FFModel(FFConfig(
        batch_size=1, num_devices=1, compute_dtype=cfg["precision"],
        serving_slots=cfg["deployment"]["serving_slots"],
        kv_page_size=cfg["window_size"], **ffconfig))
    build_evabyte(ff, 1, cfg["n_positions"], **fam.published(cfg))
    ff.compile(devices=jax.devices()[:1], defer_weights=True)
    ff.set_weights(fam.make_weights(cfg, SEED, "program"))
    return ff


@pytest.fixture(scope="module")
def model():
    return holder()


def reference_logits(tokens):
    """Head 0 of the reference's full forward over `tokens`."""
    return np.asarray(fam.logits_fn(
        fam.make_weights(CFG, SEED, "reference"), np.asarray(tokens),
        "float32"))[:, :D.v]


# -- 1. prefill in chunks, then decode, through the scheduler ---------------------
#: prompt lengths: inside the first window; a prompt that ends on a
#: chunk's last position; one past a window; three windows and a
#: partial chunk
PROMPTS = (9, 12, 17, 50)
#: `prefill_chunk`: shorter than, equal to and several times EVA's
#: chunk of 4 (10 is neither a divisor of the window of 16 nor whole
#: chunks: a pass may start and end anywhere)
CHUNKS = (0, 3, 4, 10, 12)


def serve(model, chunk, prompts, new_tokens, slots=SLOTS):
    """One scheduler over the toy model: every prompt at once (more
    than `slots` queue for one), `new_tokens` each: (recorded rows,
    handles, stats)."""
    from flexflow_tpu.serving.scheduler import ContinuousScheduler

    sched = ContinuousScheduler.from_trained(
        model, batch_slots=slots, page_size=D.w, prefill_chunk=chunk,
        prefix_cache=False, devices=jax.devices()[:1])
    rec = Recorder(sched)
    try:
        handles = [sched.generate_async(p, n, 0.0)
                   for p, n in zip(prompts, new_tokens)]
        for h in handles:
            h.wait(300)
        stats = sched.stats()
    finally:
        sched.close(10)
    return rec.rows, handles, stats


def prompts_of(lengths, seed=5):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, D.v, n).tolist() for n in lengths]


@pytest.fixture(scope="module", params=CHUNKS)
def served(request, model):
    """Four prompts over three slots: the fourth takes a slot that a
    longer or a shorter sequence has left.  7 new tokens: a decode that
    crosses a chunk's end from every prompt, and a window's end from
    the prompt of 12 (positions 12..18) and of 50 is in its fourth
    window."""
    return serve(model, request.param, prompts_of(PROMPTS), [7] * 4)


@pytest.mark.parametrize("which", range(len(PROMPTS)))
def test_served_logits_equal_the_reference_full_forward(served, which):
    rows, handles, _ = served
    h = handles[which]
    want = reference_logits(h.result)
    mine = [(pos, logits) for req, pos, logits in rows if req is h]
    # every position from the prompt's last chunk remainder on
    assert len(mine) >= 7 and max(p for p, _ in mine) == len(h.result) - 2
    for pos, logits in mine:
        assert logits.shape == (D.v,)  # head 0 alone comes back
        close(logits, want[pos], LOGIT_TOL)


def test_long_decode_crosses_two_window_ends(model):
    """A prompt of 13 and 30 new tokens: positions 13..42 are decoded a
    token a step across the ends of the first and the second window,
    every summary written by the one-token step."""
    rows, (h,), stats = serve(model, 4, prompts_of((13,)), [30], slots=2)
    want = reference_logits(h.result)
    assert len(h.result) == 43
    for _, pos, logits in rows:
        close(logits, want[pos], LOGIT_TOL)
    # chunks 3..9 end in a decode step (positions 15, 19, .. 39); the
    # prefill pass of 4 wrote chunks 0..2 or left their ends to it
    assert stats["eva"]["decode_eva_summaries_written"] >= 7 * D.L


def test_a_reused_slot_serves_what_a_fresh_server_serves(model):
    """A long sequence (4 windows) and then a short one in the SAME
    slot: the short one's logits equal the reference's, which rows of
    the first tenant's window or store, left where they were (nothing
    zeroes them), would not give if they could be read."""
    rows, handles, stats = serve(model, 4, prompts_of((50, 6)), [6, 5],
                                 slots=1)
    assert stats["requests_done"] == 2
    for h in handles:
        want = reference_logits(h.result)
        for req, pos, logits in rows:
            if req is h:
                close(logits, want[pos], LOGIT_TOL)


def test_two_rows_of_one_dispatch_in_different_windows(model):
    """Prompts of 5 and 37 admitted together: every dispatch holds a
    row in the first window and one in the third."""
    rows, handles, _ = serve(model, 4, prompts_of((5, 37)), [8, 8],
                             slots=2)
    want = {id(h): reference_logits(h.result) for h in handles}
    together = {}
    for req, pos, logits in rows:
        close(logits, want[id(req)][pos], LOGIT_TOL)
        together.setdefault(id(req), []).append(pos // D.w)
    assert set(together[id(handles[0])]) == {0}
    assert set(together[id(handles[1])]) >= {2}


# -- 2. the counters ----------------------------------------------------------------
def test_row_counts_are_arithmetic_from_the_lengths():
    # window 16, chunk 4, a store of 16 rows, 3 slots; a decode step of
    # rows at 5 and 37: 6 and 6 singletons, 0 and 8 summaries
    got = eva_row_counts(16, 4, 16, 3, [5, 37, 0], [1, 1, 0])
    assert got == {"eva_rows_window": 12, "eva_rows_summary": 8,
                   "eva_rows_read": 3 * 32, "eva_summaries_written": 0}
    # positions 7 and 39 end a chunk
    assert eva_row_counts(16, 4, 16, 3, [7, 39, 3], [1, 1, 0])[
        "eva_summaries_written"] == 2
    # a prefill pass of 6 from 14: positions 14..19 see 15, 16, 1, 2, 3, 4
    # singletons, and the four in the second window 4 summaries each;
    # it ends chunk 3 (position 15) and chunk 4 (19)
    got = eva_row_counts(16, 4, 16, 3, [14, 0, 0], [6, 0, 0])
    assert got == {"eva_rows_window": 41, "eva_rows_summary": 16,
                   "eva_rows_read": 96, "eva_summaries_written": 2}


def test_dispatch_spans_carry_the_counters_and_the_twin_its_bytes():
    from flexflow_tpu.obs.trace import next_span_id, spans
    from flexflow_tpu.serving import build_front

    first = next_span_id()
    front = build_front(holder(prefill_chunk=6))
    try:
        out = front.generate(list(range(1, 20)), 4, 0.0)
        replicas = front.stats()["replicas"]
    finally:
        front.close()
    assert len(out) == 23
    slots = CFG["deployment"]["serving_slots"]
    mine = [r for r in spans() if r.span_id > first]
    twin = next(r for r in mine if r.name == "serve.build_twin")
    # 2 layers x 4 slots x (16 + 64 / 4 + 4 pending) rows x (k, v) x 2 heads
    # x 16 x 4 B
    assert twin.args["eva_state_bytes"] == 2 * 4 * 36 * 2 * 2 * 16 * 4
    assert twin.args["eva_state_bytes"] == fam.eva_state_bytes(CFG)
    assert "rstate_bytes" not in twin.args
    read = D.L * slots * (D.w + D.p // D.c)
    decode = [r.args for r in mine if r.name == "sched.decode.dispatch"]
    prefill = [r.args for r in mine if r.name == "sched.prefill.dispatch"]
    assert decode and prefill
    # one request: passes of 6 from 0 while more than its last prompt
    # token is left (no step between them), then steps
    at = 0
    for a in prefill:
        n = a["tokens"]
        want = eva_row_counts(D.w, D.c, D.p // D.c, slots, [at], [n])
        assert {k: a[k] for k in want} == {
            k: v * D.L for k, v in want.items()}
        assert a["decode_rows"] == 0 and a["rows"] == 1
        at += n
    assert at == 18
    assert all(a["eva_rows_read"] == read for a in decode + prefill)
    assert all("rstate_rows_live" not in a for a in decode + prefill)
    # the last decode step is at position 21 (the 23rd token is its)
    last = decode[-1]
    assert last["eva_rows_window"] == D.L * (21 % D.w + 1)
    assert last["eva_rows_summary"] == D.L * (D.w // D.c)
    (r,) = replicas
    assert r["eva"]["state_bytes"] == fam.eva_state_bytes(CFG)
    assert r["eva"]["decode_dispatches"] == len(decode)
    assert r["eva"]["prefill_dispatches"] == len(prefill)
    written = sum(a["eva_summaries_written"] for a in decode + prefill)
    assert written == D.L * (22 // D.c)  # chunks that ended by position 21
    # what the readers' floors count
    assert fam.eva_read_bytes(CFG, 3) == 3 * 2 * D.h * D.hd * 4


# -- 3. admission by what a sequence holds -------------------------------------------
def test_a_twin_without_pools_admits_by_slot_whatever_pool_was_asked(model):
    """Every slot's window and store exist with the twin, so a slot is
    all a sequence needs: a pool of 2 blocks (one window of one
    sequence) would refuse what is already allocated, and is widened."""
    from flexflow_tpu.serving.scheduler import (ContinuousScheduler,
                                                PagedKVDecodeModel)

    twin = PagedKVDecodeModel(model, batch_slots=2, page_size=D.w,
                              num_blocks=2, prefix_cache=False,
                              devices=jax.devices()[:1])
    assert twin.kv_block_bytes == 0 and not twin._pools
    assert twin.num_blocks == 1 + 2 * (D.p // D.w)
    assert twin.groups["eva"].geometry == {
        "window": D.w, "chunk": D.c, "store_rows": D.p // D.c,
        "layers": D.L, "state_bytes": 2 * fam.eva_state_bytes(CFG) // 4}
    assert list(twin.groups) == ["eva"]
    assert twin.rstate_bytes == 0 and twin.has_slot_state
    sched = ContinuousScheduler(twin)
    try:
        # two sequences of the full 64 positions at once, a third queued
        hs = [sched.generate_async(p, 64 - len(p), 0.0)
              for p in prompts_of((40, 30, 8))]
        for h in hs:
            assert len(h.wait(300)) == 64
        stats = sched.stats()
    finally:
        sched.close(10)
    assert stats["requests_done"] == 3
    assert stats["kv_pool"]["bytes_per_token"] == 0


# -- 4. what the family refuses, by name -----------------------------------------------
def _front(**ffconfig):
    from flexflow_tpu.serving import build_front

    return build_front(holder(**ffconfig))


def _dense_cache():
    from flexflow_tpu.decoding import make_decoder

    return make_decoder(holder(), batch_size=2, devices=jax.devices()[:1])


NOT_CARRIED = {
    "prefix_cache": lambda: _front(prefix_cache=True),
    "speculative": lambda: _front(spec_decode="ngram"),
    "handoff": lambda: _front(serving_handoff=True),
    "tensor_parallel": lambda: _front(serving_tp=2),
    "dense_cache": _dense_cache,
}


@pytest.mark.parametrize("feature", sorted(NOT_CARRIED))
def test_feature_not_carried_is_a_config_error_by_name(feature):
    with pytest.raises(ConfigError) as err:
        NOT_CARRIED[feature]()
    assert "evabyte does not carry" in str(err.value)
    assert feature in str(err.value)


def test_a_prefill_chunk_longer_than_the_window_is_refused_by_name():
    with pytest.raises(ConfigError) as err:
        _front(prefill_chunk=40)
    assert "prefill_chunk 40" in str(err.value)
    assert "window_size 16" in str(err.value)


def test_positions_must_be_whole_chunks():
    ff = FFModel(FFConfig(batch_size=1, num_devices=1))
    with pytest.raises(ConfigError, match="multiple of chunk_size"):
        build_evabyte(ff, 1, 1, **dict(fam.published(CFG)),
                      decode_max_seq=62, kv_page_size=2)
