"""KV-cache incremental decoding (flexflow_tpu/decoding.py).

The decode twin must reproduce the O(T^2) re-forward generation
exactly: same weights, same math, one attention row at a time.  Covers
the host-loop driver, the single-program lax.scan driver, weight
transfer/introspection, and cache-state reset between sequences.
"""
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # search/train-heavy: full tier only


from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.decoding import (
    gpt_beam_search_cached,
    gpt_generate_cached,
    gpt_generate_scan,
    make_decoder,
)
from flexflow_tpu.models.transformer import (
    build_gpt,
    gpt_beam_search,
    gpt_generate,
)

V, S, B = 32, 12, 4


def _trained_gpt(devices8, steps=40):
    ff = FFModel(FFConfig(batch_size=B, num_devices=1))
    build_gpt(ff, batch_size=B, seq_length=S, hidden_size=32,
              num_layers=2, num_heads=4, intermediate_size=64,
              vocab_size=V)
    ff.compile(optimizer=SGDOptimizer(lr=0.5),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=devices8[:1])
    rng = np.random.RandomState(0)
    start = rng.randint(0, V, (B, 1))
    step = rng.randint(1, 6, (B, 1))
    seq_ids = (start + step * np.arange(S + 1)) % V
    ids = seq_ids[:, :-1].astype(np.int32)
    labels = seq_ids[:, 1:].astype(np.int32)
    pos = np.broadcast_to(np.arange(S, dtype=np.int32), (B, S)).copy()
    for _ in range(steps):
        ff.train_step({"input": ids, "positions": pos}, labels)
    return ff, ids


def test_cached_decode_matches_full_forward(devices8):
    ff, ids = _trained_gpt(devices8)
    ffd = make_decoder(ff, devices=devices8[:1])
    prompt = ids[:, :5]
    full = gpt_generate(ff, prompt, max_new_tokens=6)
    cached = gpt_generate_cached(ffd, prompt, max_new_tokens=6)
    np.testing.assert_array_equal(full, cached)


def test_scan_decode_matches_full_forward(devices8):
    ff, ids = _trained_gpt(devices8)
    ffd = make_decoder(ff, devices=devices8[:1])
    prompt = ids[:, :5]
    full = gpt_generate(ff, prompt, max_new_tokens=6)
    scanned = gpt_generate_scan(ffd, prompt, max_new_tokens=6)
    np.testing.assert_array_equal(full, scanned)


def test_cache_reset_between_sequences(devices8):
    """A second generation with a different prompt must not see stale
    cache rows from the first."""
    ff, ids = _trained_gpt(devices8)
    ffd = make_decoder(ff, devices=devices8[:1])
    p1, p2 = ids[:, :5], ids[:, 3:8]
    out2_fresh = gpt_generate_cached(ffd, p2, 4)
    _ = gpt_generate_cached(ffd, p1, 4)
    out2_again = gpt_generate_cached(ffd, p2, 4)
    np.testing.assert_array_equal(out2_fresh, out2_again)


def test_cached_sampling_runs(devices8):
    ff, ids = _trained_gpt(devices8, steps=5)
    ffd = make_decoder(ff, devices=devices8[:1])
    prompt = ids[:, :4]
    out = gpt_generate_cached(ffd, prompt, 5, temperature=0.8,
                              top_k=8, top_p=0.9, seed=3)
    assert out.shape == (B, 9)
    assert (out >= 0).all() and (out < V).all()
    np.testing.assert_array_equal(out[:, :4], prompt)
    # scan path with temperature
    s = gpt_generate_scan(ffd, prompt, 5, temperature=0.8, seed=3)
    assert s.shape == (B, 9) and (s >= 0).all() and (s < V).all()


def test_decoder_introspection_rejects_non_gpt(devices8):
    ff = FFModel(FFConfig(batch_size=2, num_devices=1))
    x = ff.create_tensor([2, 8], name="x")
    ff.dense(x, 4)
    with pytest.raises(ValueError):
        make_decoder(ff)


def test_decode_graph_rejects_kv_append():
    """decode mode refuses add_bias_kv/add_zero_attn (the cache layout
    has no slot for appended bias rows)."""
    from flexflow_tpu.ops.op import ShapeError

    ff = FFModel(FFConfig(batch_size=2, num_devices=1))
    t = ff.create_tensor([2, 1, 32], name="x")
    with pytest.raises(ShapeError):
        ff.multihead_attention(t, t, t, 32, 4, add_bias_kv=True,
                               decode_max_seq=16)


def test_forward_refuses_decode_graph(devices8):
    """forward()/eval on a decode graph would drop the cache updates
    and compute against cache_pos=0 forever — it must raise."""
    ff, ids = _trained_gpt(devices8, steps=1)
    ffd = make_decoder(ff, devices=devices8[:1])
    with pytest.raises(RuntimeError, match="decode_step"):
        ffd.forward({"input": ids[:, :1],
                     "positions": np.zeros((B, 1), np.int32)})


def test_decode_guard_syncs_from_device_state(devices8):
    """The host-side overflow-guard counter rebuilds from the device
    cache_pos after an external state swap (checkpoint restore path)."""
    ff, ids = _trained_gpt(devices8, steps=1)
    ffd = make_decoder(ff, devices=devices8[:1])
    ffd.reset_decode_state()
    for t in range(3):
        ffd.decode_step({"input": ids[:, t:t + 1],
                         "positions": np.full((B, 1), t, np.int32)})
    saved = ffd._state
    ffd.reset_decode_state()
    ffd._state = saved          # external swap, shadow counter stale at 0
    ffd.sync_decode_pos()       # what checkpoint.restore now does
    assert ffd._decode_pos == 3


def test_decode_cache_uses_compute_dtype(devices8):
    """KV caches materialize in the compute dtype (bf16) — an f32 cache
    would double HBM footprint and cast the whole cache every token."""
    import jax.numpy as jnp

    from flexflow_tpu.models.transformer import build_gpt

    ff = FFModel(FFConfig(batch_size=2, num_devices=1,
                          compute_dtype="bfloat16"))
    build_gpt(ff, batch_size=2, seq_length=8, hidden_size=16,
              num_layers=1, num_heads=2, intermediate_size=32,
              vocab_size=V, decode_max_seq=8)
    ff.compile(optimizer=SGDOptimizer(lr=0.1),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=devices8[:1])
    caches = [v for entries in ff._state.values()
              for k, v in entries.items() if k in ("k_cache", "v_cache")]
    assert caches and all(c.dtype == jnp.bfloat16 for c in caches)


def test_scan_generate_one_program_per_total(devices8):
    """Prompt length is a traced operand: two different plens with the
    same total reuse one compiled scan program."""
    ff, ids = _trained_gpt(devices8, steps=1)
    ffd = make_decoder(ff, devices=devices8[:1])
    gpt_generate_scan(ffd, ids[:, :4], max_new_tokens=5)   # total 9
    gpt_generate_scan(ffd, ids[:, :6], max_new_tokens=3)   # total 9
    assert len(ffd._scan_gen_cache) == 1
    # and the varying-plen outputs still match the host-loop driver
    a = gpt_generate_scan(ffd, ids[:, :6], max_new_tokens=3)
    b = gpt_generate_cached(ffd, ids[:, :6], max_new_tokens=3)
    np.testing.assert_array_equal(a, b)


def test_cached_beam_search_matches_full_forward(devices8):
    """The O(T) KV-cached beam search reproduces the O(T^2) reference
    path exactly: same tokens, same score (single prompt)."""
    ff, ids = _trained_gpt(devices8)
    ffd = make_decoder(ff, devices=devices8[:1])  # batch B=4 beams
    prompt = ids[:1, :5]
    want_toks, want_score = gpt_beam_search(ff, prompt, max_new_tokens=6,
                                            beam_size=4)
    got_toks, got_scores = gpt_beam_search_cached(
        ffd, prompt, max_new_tokens=6, beam_size=4)
    np.testing.assert_array_equal(got_toks[0], want_toks)
    assert abs(got_scores[0] - want_score) < 1e-4


def test_cached_beam_search_eos_and_length_penalty(devices8):
    """eos freezing and GNMT length normalization agree with the
    reference path (frozen beams compete at their final score)."""
    ff, ids = _trained_gpt(devices8)
    ffd = make_decoder(ff, devices=devices8[:1])
    prompt = ids[:1, :4]
    eos = int(ids[0, 6])  # an id the greedy continuation will hit
    want_toks, want_score = gpt_beam_search(
        ff, prompt, max_new_tokens=7, beam_size=4,
        length_penalty=0.6, eos_id=eos)
    got_toks, got_scores = gpt_beam_search_cached(
        ffd, prompt, max_new_tokens=7, beam_size=4,
        length_penalty=0.6, eos_id=eos)
    np.testing.assert_array_equal(got_toks[0], want_toks)
    assert abs(got_scores[0] - want_score) < 1e-4


def test_cached_beam_search_batched_prompts(devices8):
    """A batch of prompts decodes in one pass and matches per-prompt
    full-forward beam search (cache-row reordering keeps each row's
    cache consistent with its hypothesis)."""
    ff, ids = _trained_gpt(devices8)
    ffd = make_decoder(ff, devices=devices8[:1])  # batch 4 = 2x2
    prompts = np.stack([ids[0, :5], ids[2, 1:6]])
    got_toks, got_scores = gpt_beam_search_cached(
        ffd, prompts, max_new_tokens=5, beam_size=2)
    for p in range(2):
        want_toks, want_score = gpt_beam_search(
            ff, prompts[p], max_new_tokens=5, beam_size=2)
        np.testing.assert_array_equal(got_toks[p], want_toks)
        assert abs(got_scores[p] - want_score) < 1e-4


def test_decode_overflow_guard(devices8):
    """Stepping past decode_max_seq raises instead of silently
    clamping the cache write (device dynamic_update_slice clamps)."""
    ff, ids = _trained_gpt(devices8, steps=1)
    ffd = make_decoder(ff, devices=devices8[:1])
    ffd.reset_decode_state()
    for t in range(S):
        ffd.decode_step({"input": ids[:, t:t + 1],
                         "positions": np.full((B, 1), t, np.int32)})
    with pytest.raises(ValueError, match="decode_max_seq"):
        ffd.decode_step({"input": ids[:, :1],
                         "positions": np.full((B, 1), S - 1, np.int32)})
    ffd.reset_decode_state()  # guard resets with the caches
    ffd.decode_step({"input": ids[:, :1],
                     "positions": np.zeros((B, 1), np.int32)})
