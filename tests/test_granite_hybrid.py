"""The granitemoehybrid language model (Mamba-2 layers whose state lives
in per-slot arrays beside a paged grouped-query cache with no positional
encoding, a head tied to the embedding's table, Granite's four
multipliers) against its plain float32 reference
(benchmarks/families/granite_hybrid.py) on seeded weights, at a toy size
on the CPU, comparing LOGITS.

Tolerances.  The program and the reference compute the same float32
arithmetic in another order (a chunk's matrix form from a carried state
against one scan a position over the sequence, grouped heads against
repeated ones), so they differ by rounding only: 1e-5 of the compared
tensor's largest magnitude for one op, 5e-5 for logits that went
through ten layers and were multiplied by 12 on the way in.  A
multiplier left out moves the logits by 0.3 to 1.5 of their magnitude
(the embedding's, the residual's, the head's) or by 2e-3 (the
attention's: one layer of ten, and the toy's scores are small):
`MISSED` (1e-3, twenty times the tolerance) stands between.
"""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _family import Recorder, close, config, op_alone, padded, reference_side

from benchmarks.families import granite_hybrid as fam
from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.config import ConfigError
from flexflow_tpu.models.granite_hybrid import build_granite_hybrid
from flexflow_tpu.ops import mamba2
from flexflow_tpu.ops.mamba2 import Mamba2Mixer, Mamba2Params

CFG = config("toy-granite-hybrid.json")
D = fam.dims(CFG)
SEED = 11
OP_TOL, LOGIT_TOL, MISSED = 1e-5, 5e-5, 1e-3
MAMBA_LAYER, ATTENTION_LAYER = 0, D.types.index("attention")
PARAMS = Mamba2Params(embed_dim=D.e, num_heads=D.H, head_dim=D.P,
                      state_dim=D.N, conv_kernel=D.K, chunk_size=8,
                      eps=D.eps)


def holder(cfg=CFG, **ffconfig):
    """The served model's holder with the seed's weights set."""
    dep = cfg["deployment"]
    ffconfig.setdefault("prefix_cache", False)
    ffconfig.setdefault("prefill_chunk", dep["prefill_chunk"])
    ff = FFModel(FFConfig(
        batch_size=1, num_devices=1, compute_dtype=cfg["precision"],
        serving_slots=dep["serving_slots"], kv_page_size=dep["kv_page_size"],
        kv_pool_blocks=dep["kv_pool_blocks"], **ffconfig))
    build_granite_hybrid(ff, 1, cfg["n_positions"], **fam.published(cfg))
    ff.compile(devices=jax.devices()[:1], defer_weights=True)
    ff.set_weights(fam.make_weights(cfg, SEED, "program"))
    return ff


def reference_logits(tokens, cfg=CFG):
    return np.asarray(fam.logits_fn(
        fam.make_weights(cfg, SEED, "reference"), padded(tokens),
        "float32"))[:len(tokens)]


# -- 1. the recurrence's forms -----------------------------------------------
def recurrence_case(b, s, seed=3):
    """Seeded operands of a recurrence over [b, s]: (S, x, B, C, dt, A,
    D), decays spread as the seeded model's."""
    k = jax.random.split(jax.random.key(seed), 7)
    h, p, n = D.H, D.P, D.N
    S = jax.random.normal(k[0], (b, h, p, n))
    dt = jnp.exp(jax.random.uniform(k[4], (b, s, h), minval=np.log(1e-3),
                                    maxval=np.log(0.5)))
    return (S, jax.random.normal(k[1], (b, s, h, p)),
            jax.random.normal(k[2], (b, s, n)),
            jax.random.normal(k[3], (b, s, n)), dt,
            -jax.random.uniform(k[5], (h,), minval=1.0, maxval=16.0),
            1.0 + 0.1 * jax.random.normal(k[6], (h,)))


@pytest.mark.parametrize("s", [8, 16, 13, 5, 1])
def test_chunked_form_equals_the_scan_a_position(s):
    """Whole chunks of 8, a last chunk that is not whole, less than one
    chunk, one position: the state and every output."""
    case = recurrence_case(2, s)
    want_S, want_y = jax.jit(mamba2.ssm_scan)(*case)
    got_S, got_y = jax.jit(mamba2.ssd_chunked, static_argnums=7)(*case, 8)
    close(got_y, want_y, OP_TOL)
    close(got_S, want_S, OP_TOL)


# -- 2. the op: alone, in chunks from carried state, rows that stay -----------------
MAMBA_LEAVES = {k[len("mamba/"):]: v
                for k, v in fam.leaf_shapes(D, "mamba").items()
                if k.startswith("mamba/")}


def test_op_alone_matches_the_reference_and_its_gradient():
    """The stateless op (two chunks of 8) against the reference's scan a
    position, output and gradients of the input and of every leaf."""
    def plain(x, w):
        return fam.mamba(x, {f"mamba/{k}": v for k, v in w.items()}, D,
                         lambda v: v)

    case = reference_side(plain, MAMBA_LEAVES, D.e)
    op = op_alone(lambda ff, x, _: ff.mamba2_mixer(x, PARAMS, name="op"),
                  case, grad_tol=5e-5)
    assert op.slot_state_entries() == () and op.dispatch_group() is None


def slot_op(slots, step):
    ff = FFModel(FFConfig(batch_size=slots, num_devices=1))
    x = ff.create_tensor([slots, step, D.e], name="x")
    return ff.mamba2_mixer(x, PARAMS, name="op", slot_state=True).owner_op


@pytest.fixture(scope="module")
def op_case():
    """(x [3, 13, e], the op's eight weights, the one-shot forward)."""
    keys = jax.random.split(jax.random.key(17), 10)
    x = jax.random.normal(keys[0], (3, 13, D.e))
    w = [0.3 * jax.random.normal(k, s) + (1.0 if n in ("norm", "D") else 0.0)
         for k, (n, s) in zip(keys[1:], MAMBA_LEAVES.items())]
    ff = FFModel(FFConfig(batch_size=3, num_devices=1))
    whole = ff.mamba2_mixer(ff.create_tensor([3, 13, D.e], name="x"), PARAMS,
                            name="op").owner_op
    return x, w, jax.jit(lambda x, w: whole.forward([x], w)[0])(x, w)


def zero_state(slots):
    return (jnp.zeros((slots, D.K - 1, D.conv_dim)),
            jnp.zeros((slots, D.H, D.P, D.N)))


def test_chunks_then_single_steps_equal_the_one_shot_forward(op_case):
    """13 positions as two chunks of 4 with the conv tail and the state
    carried, then five single steps."""
    x, w, want = op_case
    chunk, one = slot_op(3, 4), slot_op(3, 1)
    run = {4: jax.jit(lambda x, w, t, S, n: chunk.forward([x], w + [t, S, n])),
           1: jax.jit(lambda x, w, t, S, n: one.forward([x], w + [t, S, n]))}
    (tail, S), at, got = zero_state(3), 0, []
    for step in (4, 4, 1, 1, 1, 1, 1):
        out, tail, S, _ = run[step](x[:, at:at + step], w, tail, S,
                                    jnp.full((3,), step, jnp.int32))
        got.append(out)
        at += step
    close(jnp.concatenate(got, axis=1), want, OP_TOL)


def test_rows_that_do_not_advance_keep_their_state_to_the_byte(op_case):
    """`row_tokens` 0: the conv tail and the state are the input's
    bytes; pads past `row_tokens` move neither (row 1 fed 2 real tokens
    of a chunk of 4 holds what 2 single steps leave)."""
    x, w, _ = op_case
    chunk, one = slot_op(3, 4), slot_op(3, 1)
    keys = jax.random.split(jax.random.key(23), 2)
    tail = jax.random.normal(keys[0], (3, D.K - 1, D.conv_dim))
    S = jax.random.normal(keys[1], (3, D.H, D.P, D.N))
    counts = jnp.asarray([4, 2, 0], jnp.int32)
    out, tail1, S1, _ = jax.jit(
        lambda: chunk.forward([x[:, :4]], w + [tail, S, counts]))()
    for got, was in ((tail1, tail), (S1, S)):
        np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(was[2]))
    t, s = tail, S
    for at in range(2):
        o, t, s, _ = one.forward([x[:, at:at + 1]],
                                 w + [t, s, jnp.ones((3,), jnp.int32)])
        close(out[1, at], o[1, 0], OP_TOL)
    close(tail1[1], t[1], OP_TOL)
    close(S1[1], s[1], OP_TOL)


def test_flops_count_the_recurrence_and_state_sizes_at_published_widths():
    real = config("granite-4.0-h-micro-serve.json")
    d = fam.dims(real)
    p = Mamba2Params(embed_dim=d.e, num_heads=d.H, head_dim=d.P,
                     state_dim=d.N, conv_kernel=d.K)
    assert (p.d_inner, p.conv_dim) == (4096, 4352)
    ff = FFModel(FFConfig(batch_size=1, num_devices=1))
    op = ff.mamba2_mixer(ff.create_tensor([1, 1, d.e], name="x"), p,
                         name="op").owner_op
    assert op.flops() == (2.0 * 2048 * (2 * 4096 + 4352 + 64) + 2.0 * 128
                          + 2.0 * 4352 * 4 + 5.0 * 64 * 64 * 128)
    c = fam.parameter_counts(d)
    assert c["mamba_mixers"] // 36 == 25_847_232
    assert c["attention_mixers"] // 4 == 10_485_760
    assert c["mlps"] // 40 == 50_331_648 and c["table"] == 205_520_896
    assert fam.parameters(real) == 3_191_396_096  # the table ONCE
    assert fam.ssm_state_bytes(real, 1) == 36 * 64 * 64 * 128 * 4
    assert fam.rstate_row_bytes(real) == 75_497_472 + 36 * 3 * 4352 * 2
    assert fam.kv_block_bytes(real) == 16 * 8192


# -- 3. prefill through the pass, then decode, behind build_front --------------------
def serve_toy():
    """A front over the toy model (the one-pass prefill program, the
    lookahead loop, the paged pool, per-slot state): a long prompt
    prefilled in chunks alone, three prompts at once, then a request
    into a slot that an earlier one used: (recorded rows, handles,
    the replica's stats, the spans made)."""
    from flexflow_tpu.obs.trace import next_span_id, spans
    from flexflow_tpu.serving import build_front

    first = next_span_id()
    front = build_front(holder())
    rec = Recorder(front.replicas[0].scheduler)
    try:
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, D.v, n).tolist() for n in (21, 15, 9, 5)]
        results = [front.generate_async(prompts[0], 6, 0.0).wait(300)]
        handles = [front.generate_async(p, 5, 0.0) for p in prompts[:3]]
        results += [h.wait(300) for h in handles]
        results.append(front.generate_async(prompts[3], 4, 0.0).wait(300))
        stats = front.stats()["replicas"][0]
    finally:
        front.close(10)
    return rec.rows, results, stats, [r for r in spans()
                                      if r.span_id > first]


def reference_by_prompt(results, cfg=CFG):
    """request -> the reference's logits over the longest sequence
    served from its prompt (greedy: a shorter one is its prefix)."""
    by_length = sorted(results, key=len, reverse=True)
    return lambda req: reference_logits(next(
        tokens for tokens in by_length
        if tokens[:len(req.prompt)] == list(req.prompt)), cfg)


@pytest.fixture(scope="module")
def served():
    return serve_toy()


def test_served_logits_equal_the_reference_full_forward(served):
    rows, results, stats, _ = served
    want, cache = reference_by_prompt(results), {}
    assert len(rows) >= 25
    for req, pos, logits in rows:
        ref_logits = cache.setdefault(tuple(req.prompt), None)
        if ref_logits is None:
            ref_logits = cache[tuple(req.prompt)] = want(req)
        close(logits, ref_logits[pos], LOGIT_TOL)
    # the same prompt into the slot its first tenant left: the same
    # tokens, which a state left behind would not give
    assert results[0][:-1] == results[1]
    assert stats["requests_done"] == 5 and stats["prefill_passes"] == 1


@pytest.mark.parametrize("key", ["embedding_multiplier",
                                 "residual_multiplier", "logits_scaling",
                                 "attention_multiplier"])
def test_a_multiplier_left_out_fails_the_comparison(served, key):
    """The reference with ONE of the four multipliers at 1 is another
    model: the served logits stand far from it, so a program that
    dropped the multiplier could not pass."""
    rows, results, _, _ = served
    want = reference_logits(results[0], dict(copy.deepcopy(CFG), **{key: 1}))
    mine = [(pos, logits) for req, pos, logits in rows
            if list(req.prompt) == results[0][:len(req.prompt)]]
    assert mine
    err = max(float(np.max(np.abs(logits - want[pos])))
              / float(np.max(np.abs(want[pos]))) for pos, logits in mine)
    assert err > MISSED, (key, err)


def test_rstate_counts_on_both_dispatch_spans_and_in_stats(served):
    """The plain form (the only one built) touches every slot."""
    _, _, stats, spans = served
    slots, r = CFG["deployment"]["serving_slots"], stats["rstate"]
    for program in ("decode", "prefill"):  # (the sums are by program)
        n, live, touched = (r[f"{program}_{k}"] for k in (
            "dispatches", "rstate_rows_live", "rstate_rows_touched"))
        assert slots * n >= live > 0 and touched == slots * n
    assert r["layers"] == D.mamba_layers == 9
    # 9 layers x (8 heads x 16 x 16 + a tail of 3 x 160) float32, 4 slots
    assert r["state_bytes"] == 9 * (8 * 16 * 16 + 3 * 160) * 4 * slots
    assert r["state_bytes"] == fam.rstate_row_bytes(CFG) * slots
    assert (r["ssm_kernel_ops"], r["ssm_plain_ops"]) == (0, 9)
    twin = next(s for s in spans if s.name == "serve.build_twin")
    assert twin.args["rstate_bytes"] == r["state_bytes"]
    assert (twin.args["ssm_kernel_ops"], twin.args["ssm_plain_ops"]) == (0, 9)
    for name in ("sched.decode.dispatch", "sched.prefill.dispatch"):
        got = [s.args for s in spans if s.name == name]
        assert got and all(0 < a["rstate_rows_live"] <= slots
                           and a["rstate_rows_touched"] == slots
                           and "kv_blocks_live" in a for a in got), name


def test_the_group_counts_every_slot_touched_at_both_step_lengths():
    """The op's group by itself, no server: the plain form reads and
    writes every slot's state in the decode step and in the pass, while
    `rstate_rows_live` counts the rows that advance."""
    twin_ops = [slot_op(3, 1) for _ in range(2)]
    told = Mamba2Mixer.dispatch_group_of(
        twin_ops, family="granitemoehybrid", batch_slots=3, page_size=4,
        max_seq=64, prefill_chunk=4, state_bytes=7)
    at, two = [5, 9, 0], [1, 1, 0]  # two of three rows advance
    for step in (1, 4):
        assert told.counts(at, two, step) == {"rstate_rows_live": 2,
                                              "rstate_rows_touched": 3}
    assert told.geometry == {"ssm_kernel_ops": 0, "ssm_plain_ops": 2}
    assert told.build_args == {"rstate_bytes": 7, **told.geometry}


def test_twin_names_its_state_and_keeps_pages_and_slot_state_apart():
    from flexflow_tpu import decoding
    from flexflow_tpu.serving.scheduler import PagedKVDecodeModel

    model = PagedKVDecodeModel(holder(), batch_slots=3, page_size=4,
                               num_blocks=40, prefill_chunk=4,
                               prefix_cache=False, devices=jax.devices()[:1])
    slot = decoding.slot_state_entries(model.ffd)
    assert set(slot.values()) == {("conv_state", "ssm_state")}
    assert sorted(slot) == sorted(
        f"mamba_{i}" for i, t in enumerate(D.types) if t == "mamba")
    assert list(decoding.cache_entries(model.ffd)) == [
        f"attn_{ATTENTION_LAYER}"]
    state = model._state[f"mamba_{MAMBA_LAYER}"]
    assert state["ssm_state"].dtype == jnp.float32
    assert state["ssm_state"].shape == (3, D.H, D.P, D.N)
    assert state["conv_state"].shape == (3, D.K - 1, D.conv_dim)
    at, two = [5, 9, 0], [1, 1, 0]  # two of three rows advance
    assert model.dispatch_counts(at, two, 4) == {
        "rstate": {"rstate_rows_live": 2, "rstate_rows_touched": 3}}


NOT_CARRIED = {
    "prefix_cache": lambda: holder(prefix_cache=True),
    "dense_cache": lambda: build_granite_hybrid(
        FFModel(FFConfig(batch_size=1, num_devices=1)), 1, 1,
        **dict(fam.published(CFG), decode_max_seq=64)),
    "more_groups": lambda: build_granite_hybrid(
        FFModel(FFConfig(batch_size=1, num_devices=1)), 1, 8,
        **dict(fam.published(CFG), mamba_n_groups=2)),
    "untied": lambda: build_granite_hybrid(
        FFModel(FFConfig(batch_size=1, num_devices=1)), 1, 8,
        **dict(fam.published(CFG), tie_word_embeddings=False)),
}


@pytest.mark.parametrize("feature", sorted(NOT_CARRIED))
def test_what_is_not_built_is_a_config_error_by_name(feature):
    from flexflow_tpu.serving import build_front

    with pytest.raises(ConfigError) as e:
        made = NOT_CARRIED[feature]()
        if feature == "prefix_cache":
            build_front(made).close()
    assert "granitemoehybrid" in str(e.value)


def test_no_serving_file_names_the_op_or_the_family():
    import pathlib
    import re

    root = pathlib.Path(fam.__file__).resolve().parents[2] / "flexflow_tpu"
    files = list((root / "serving").glob("*.py")) + [root / "decoding.py"]
    assert len(files) > 10
    for path in files:
        assert not re.search(r"mamba|granite", path.read_text(), re.I), path


# -- 4. the tied head ----------------------------------------------------------------
def trainer(seq=6):
    ff = FFModel(FFConfig(batch_size=2, num_devices=1))
    build_granite_hybrid(ff, 2, seq, **dict(
        fam.published(CFG), num_hidden_layers=2,
        layer_types=["mamba", "attention"]))
    ff.compile(optimizer=SGDOptimizer(lr=0.0),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=jax.devices()[:1])
    return ff


def test_tied_table_is_one_leaf_and_round_trips():
    ff = trainer()
    weights = ff.get_weights()
    assert "lm_head" not in weights and "logits_scale" not in weights
    assert list(weights["tok_embed"]) == ["weight"]
    assert weights["tok_embed"]["weight"].shape == (D.v, D.e)
    head = next(op for op in ff.layers.topo_order() if op.name == "lm_head")
    assert head.weight_specs == []
    assert head.borrowed_weights() == (("tok_embed", "weight"),)
    assert head.flops() == 2.0 * 2 * 6 * D.e * D.v  # priced as a dense op
    changed = jax.tree.map(lambda v: v + 1.0, weights)
    ff.set_weights(changed)
    back = ff.get_weights()
    assert jax.tree.structure(back) == jax.tree.structure(weights)
    np.testing.assert_array_equal(back["tok_embed"]["weight"],
                                  changed["tok_embed"]["weight"])
    with pytest.raises(ValueError, match="not an embedding"):
        ff2 = FFModel(FFConfig(batch_size=1, num_devices=1))
        ff2.tied_dense(ff2.create_tensor([1, 2, 8], name="x"), "nowhere")


def test_tied_gradient_is_the_sum_of_both_uses():
    """d loss / d table through the program (one leaf, read by the
    lookup and by the head) against the reference's two separate
    tables' gradients added."""
    ff = trainer()
    d = fam.dims(dict(CFG, num_hidden_layers=2,
                      layer_types=["mamba", "attention"]))
    w = {k: {n: jnp.asarray(v) for n, v in e.items()}
         for k, e in ff.get_weights().items()}
    ids = jnp.asarray(np.random.default_rng(3).integers(1, D.v, (2, 6)),
                      jnp.int32)
    probe = jax.random.normal(jax.random.key(5), (2, 6, D.v))
    fwd = ff.executor.build_forward()

    def program(w):
        return jnp.sum(fwd(w, ff._state, {"input": ids}) * probe)

    got = jax.grad(program)(w)["tok_embed"]["weight"]

    def plain(lookup, head):
        with jax.default_matmul_precision("highest"):
            out = []
            for row in ids:
                x = d.embed_mult * jnp.take(lookup, row, axis=0)
                for i, kind in enumerate(d.types):
                    x = fam.layer(x, fam.ServedWeights(d, w).layer(i), d,
                                  lambda v: v, kind)
                x = fam.rms(x, w["final_norm"]["gamma"], d.eps)
                out.append(jnp.matmul(x, head.T) / d.logits_div)
            return jnp.sum(jnp.stack(out) * probe)

    table = w["tok_embed"]["weight"]
    by_lookup, by_head = jax.grad(plain, argnums=(0, 1))(table, table)
    assert float(jnp.max(jnp.abs(by_lookup))) > 0
    assert float(jnp.max(jnp.abs(by_head))) > 0
    close(got, by_lookup + by_head, 5e-5)


# -- 5. the attention's scale ---------------------------------------------------------
def attention_hlo(**fields):
    ff = FFModel(FFConfig(batch_size=2, num_devices=1))
    x = ff.create_tensor([2, 8, 32], name="x")
    op = ff.multihead_attention(x, x, x, 32, 4, causal=True, num_kv_heads=2,
                                name="op", **fields).owner_op
    shapes = [jax.ShapeDtypeStruct(s.shape.logical_shape, jnp.float32)
              for s in op.weight_specs]
    x_s = jax.ShapeDtypeStruct((2, 8, 32), jnp.float32)
    return jax.jit(lambda x, w: op.forward([x, x, x], w)[0]).lower(
        x_s, shapes).as_text()


def test_softmax_scale_none_is_the_program_it_was():
    """None (the default, what every other family builds) lowers to the
    text the explicit 1 / sqrt(head channels) lowers to; Granite's
    multiplier to another."""
    default = attention_hlo()
    assert attention_hlo(softmax_scale=None) == default
    assert attention_hlo(softmax_scale=float(1.0 / np.sqrt(8))) == default
    assert attention_hlo(softmax_scale=0.015625) != default
    p = next(op for op in holder_graph().topo_order()
             if op.name == f"attn_{ATTENTION_LAYER}").params
    assert (p.softmax_scale, p.rotary_dim, p.kv_heads) == (
        CFG["attention_multiplier"], 0, D.kvh)


def holder_graph():
    ff = FFModel(FFConfig(batch_size=1, num_devices=1))
    build_granite_hybrid(ff, 1, 8, **fam.published(CFG))
    return ff.layers


def test_reference_imports_nothing_from_the_program():
    import inspect

    src = inspect.getsource(fam)
    body = src.split("# -- the plain reference")[1].split(
        "# -- what a pass has to move")[0]
    assert "flexflow_tpu" not in body
    assert 'default_matmul_precision("highest")' in body
    # what `ref` offers the reference is the operand rounding alone
    assert set(n for n in ("rounder", "seed_key") if f"ref.{n}" in src) == {
        "rounder", "seed_key"}
