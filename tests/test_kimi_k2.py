"""The kimi_k2 language model (MLA over a paged latent cache, a share of
sigmoid-routed experts with a shared expert, RMSNorm, gated MLP)
against its plain float32 reference (benchmarks/families/kimi_k2.py) on
seeded weights, at a toy size on the CPU, comparing LOGITS.

Tolerances.  The program and the reference compute the same float32
arithmetic in another order (absorbed against expanded attention, one
batched product over experts against one expert at a time), so they
differ by rounding only: 1e-5 of the compared tensor's largest
magnitude for one op, 2e-5 for logits that went through every layer.
A bf16 run misses that by three orders of magnitude
(`test_bf16_logits_leave_the_float32_tolerance`).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _family import Recorder, close, config, padded

from benchmarks import reference as ref
from benchmarks.families import kimi_k2 as fam
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.config import ConfigError
from flexflow_tpu.models.kimi_k2 import build_kimi_k2

CFG = config("toy-kimi.json")
D = fam.dims(CFG)
SEED = 11
KEY = ref.seed_key(SEED)
OP_TOL, LOGIT_TOL = 1e-5, 2e-5


def holder(cfg=CFG, seq=None, precision=None, **ffconfig):
    """The served model's holder with the seed's weights set."""
    cfg = dict(cfg, precision=precision or cfg["precision"])
    dep = cfg["deployment"]
    ff = FFModel(FFConfig(
        batch_size=1, num_devices=1, compute_dtype=cfg["precision"],
        serving_slots=dep["serving_slots"], kv_page_size=dep["kv_page_size"],
        kv_pool_blocks=dep["kv_pool_blocks"], **ffconfig))
    build_kimi_k2(ff, 1, seq or cfg["n_positions"], **fam.published(cfg))
    ff.compile(devices=jax.devices()[:1], defer_weights=True)
    ff.set_weights(fam.make_weights(cfg, SEED, "program"))
    return ff


def reference_logits(tokens):
    return np.asarray(fam.logits_fn(
        fam.make_weights(CFG, SEED, "reference"), padded(tokens),
        "float32"))[:len(tokens)]


# -- 1. each op alone ----------------------------------------------------------
def one_op_model(build):
    """A model of input -> one op: (ff, the op's name)."""
    ff = FFModel(FFConfig(batch_size=2, num_devices=1))
    x = ff.create_tensor([2, 12, D.e], name="x")
    pos = ff.create_tensor([2, 12], dtype="int32", name="positions")
    out = build(ff, x, pos)
    ff.compile(devices=jax.devices()[:1], defer_weights=True)
    return ff, out.owner_op.name


def mla_params():
    return holder_graph_op("attn_0").params


def holder_graph_op(name):
    ff = FFModel(FFConfig(batch_size=1, num_devices=1))
    build_kimi_k2(ff, 1, 8, **fam.published(CFG))
    return next(op for op in ff.layers.topo_order() if op.name == name)


OPS = {
    "rms_norm": (
        lambda ff, x, pos: ff.rms_norm(x, D.eps, name="op"), "norm",
        lambda x, w: fam.rms(x, w["gamma"], D.eps)),
    "gated_mlp": (
        lambda ff, x, pos: ff.gated_mlp(x, D.f_dense, name="op"), "mlp",
        lambda x, w: fam.gated(x, w["w_gate"], w["w_up"], w["w_down"],
                               lambda v: v)),
    "mla": (
        lambda ff, x, pos: ff.mla_attention(x, pos, mla_params(), name="op"),
        "attn", lambda x, w: fam.attention(x, w, D, lambda v: v)),
    "routed_experts": (
        lambda ff, x, pos: ff.routed_experts(
            x, holder_graph_op("moe_1").params, name="op"),
        "moe", None),
}


@pytest.mark.parametrize("name", sorted(OPS))
def test_op_alone_matches_the_reference(name):
    build, kind, want_fn = OPS[name]
    ff, op_name = one_op_model(build)
    w = jax.tree.map(np.asarray, fam.make_op(
        KEY, 1, d=D, kind=kind, dtype=jnp.dtype("float32")))
    ff.set_weights({op_name: w})
    x = np.asarray(jax.random.normal(jax.random.key(3), (2, 12, D.e)))
    pos = np.tile(np.arange(12, dtype=np.int32), (2, 1))
    got = np.asarray(ff.forward({"x": x, "positions": pos}))
    with jax.default_matmul_precision("highest"):
        if name == "routed_experts":
            want = [sum(fam.experts(jnp.asarray(row), KEY, 1, D,
                                    lambda v: v)) for row in x]
        else:
            want = [want_fn(jnp.asarray(row), w) for row in x]
    close(got, np.stack(want), OP_TOL)


# -- 2. prefill then decode through the paged latent cache -----------------------



@pytest.fixture(scope="module")
def served():
    """One scheduler over the toy model and a scenario with chunked
    prefill, a full-prompt prefix hit (copy-on-write), a partial hit
    and a slot reused after a finished request: (recorded rows,
    handles, scheduler stats)."""
    from flexflow_tpu.serving.scheduler import ContinuousScheduler

    ff = holder()
    sched = ContinuousScheduler.from_trained(
        ff, batch_slots=3, page_size=4, num_blocks=40, prefill_chunk=4,
        prefix_cache=True, devices=jax.devices()[:1])
    rec = Recorder(sched)
    try:
        rng = np.random.default_rng(5)
        a = rng.integers(1, D.v, 16).tolist()  # four full pages
        b = a[:8] + rng.integers(1, D.v, 7).tolist()
        c = rng.integers(1, D.v, 9).tolist()
        handles = [sched.generate_async(a, 6, 0.0)]
        handles[0].wait(120)
        # a again (every block cached: the tail block is copied before
        # the first write), b (shares two blocks), c: three slots at once
        handles += [sched.generate_async(p, 5, 0.0) for p in (a, b, c)]
        for h in handles[1:]:
            h.wait(120)
        handles.append(sched.generate_async(c[:5], 4, 0.0))  # a slot again
        handles[-1].wait(120)
        stats = sched.stats()
    finally:
        sched.close(10)
    return rec.rows, handles, stats


def test_served_logits_equal_the_reference_full_forward(served):
    rows, handles, _ = served
    want = {id(h): reference_logits(h.result) for h in handles}
    assert len(rows) >= 25
    for req, pos, logits in rows:
        close(logits, want[id(req)][pos], LOGIT_TOL)


def test_served_scenario_prefilled_in_one_weight_pass_a_dispatch(served):
    """The family's recipe carries `prefill_pass`, so the scheduler
    built the one-pass program: the counter says the mechanism ran."""
    _, _, stats = served
    assert stats["prefill_chunk"] == 4 and stats["prefill_passes"] == 1
    assert stats["prefill_steps"] > 0


def test_front_reports_one_pass_a_replica_and_the_dispatch_span_carries_it():
    """Through `build_front` (the supervised model wrapper between the
    scheduler and the programs): `stats()` a replica and the `passes`
    arg of `sched.prefill.dispatch`, whose block counts are ONE view's."""
    from flexflow_tpu.obs.trace import next_span_id, spans

    front = _front(prefill_chunk=4)
    try:
        first = next_span_id()
        front.generate(list(range(1, 14)), 3, 0.0)
        replicas = front.stats()["replicas"]
    finally:
        front.close()
    assert [r["prefill_passes"] for r in replicas] == [1] * len(replicas)
    mine = [r for r in spans() if r.name == "sched.prefill.dispatch"
            and r.span_id > first]
    dep = CFG["deployment"]
    view = dep["serving_slots"] * D.p // dep["kv_page_size"]
    # while more than the last prompt token is left an iteration is the
    # pass alone: the chunks start at 0, 4 and 8 and read up to their
    # last position; the 13th token then runs through the decode step
    assert [r.args["tokens"] for r in mine] == [4, 4, 4]
    assert [r.args["kv_blocks_live"] for r in mine] == [1, 2, 3]
    assert [r.args["decode_rows"] for r in mine] == [0, 0, 0]
    for r in mine:
        assert r.args["passes"] == 1
        assert r.args["kv_blocks_dense"] == r.args["kv_blocks_read"] == view


def test_scenario_hit_the_prefix_cache_copied_a_block_and_reused_a_slot(
        served):
    _, handles, stats = served
    assert handles[1].prefix_hit_tokens >= 12      # the full-prompt hit
    assert handles[2].prefix_hit_tokens == 8       # the shared two pages
    assert stats["prefix_cache"]["cow_copies"] >= 1
    assert stats["requests_done"] == 5 and stats["prefill_steps"] > 0
    assert handles[0].result[:-1] == handles[1].result


def test_latent_cache_holds_576_values_a_token_a_layer_at_published_widths():
    """From the state's shapes, with the catalog's widths (no array is
    made: the op only states its specs)."""
    from flexflow_tpu.ops.mla import MLAParams

    ff = FFModel(FFConfig(batch_size=32, num_devices=1))
    x = ff.create_tensor([32, 1, 7168], name="x")
    pos = ff.create_tensor([32, 1], dtype="int32", name="positions")
    ff.mla_attention(x, pos, MLAParams(
        embed_dim=7168, num_heads=64, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
        name="attn", decode_max_seq=2048, kv_page_size=16,
        kv_num_blocks=4097)
    op = ff.layers.topo_order()[-1]
    state = {s.name: s.shape.logical_shape
             for s in op.weight_specs[op.num_trainable_weights():]}
    assert op.cache_entries() == ("latent_cache",)
    assert state["latent_cache"] == (4097, 16, 576)
    assert state["block_table"] == (32, 128) and state["seq_lens"] == (32,)
    assert 2 * 64 * 128 == 16384  # what full keys and values would hold


# -- 2b. a prefill chunk in one pass against the scanned seq-1 step ----------------
CHUNK, PAGE, SLOTS = 4, 4, 4
#: row -> position its chunk starts at; `rider` is a decode-phase row of
#: a prefill dispatch: an all-zero table row at position 0
STARTS = {"fills_a_page": 0, "crosses_a_page": 6, "mid_page": 3, "rider": 0}


def _pools(state):
    return {op: np.asarray(e["latent_cache"], np.float32)
            for op, e in state.items() if "latent_cache" in e}


@pytest.fixture(scope="module")
def twin():
    """The paged seq-1 twin with its decode step and both prefill
    programs (state is donated: every call gets a copy)."""
    from flexflow_tpu.decoding import (build_paged_decode_step,
                                       build_paged_prefill_pass,
                                       build_paged_prefill_step,
                                       make_decoder)

    ffd = make_decoder(holder(), batch_size=SLOTS, kv_page_size=PAGE,
                       kv_num_blocks=1 + SLOTS * D.p // PAGE,
                       devices=jax.devices()[:1])
    fns = {"step": build_paged_decode_step(ffd),
           "scan": build_paged_prefill_step(ffd, CHUNK),
           "pass": build_paged_prefill_pass(ffd, CHUNK)}
    btab = np.arange(1, 1 + SLOTS * D.p // PAGE,
                     dtype=np.int32).reshape(SLOTS, -1)

    def run(name, state, tokens, positions, table, fed=None):
        """`step`: (logits, state); `scan` / `pass`: state (the pass
        with every row fed the whole chunk unless `fed` says how many
        tokens each row really has: then (logits, state))."""
        out = fns[name](ffd._weights, jax.tree.map(jnp.copy, state),
                        jnp.asarray(tokens, jnp.int32),
                        jnp.asarray(positions, jnp.int32),
                        jnp.asarray(table, jnp.int32),
                        *((jnp.full(SLOTS, CHUNK, jnp.int32)
                           if fed is None else jnp.asarray(fed, jnp.int32),)
                          if name == "pass" else ()))
        return out[1] if name == "pass" and fed is None else out

    return ffd, run, btab


@pytest.fixture(scope="module")
def chunk_pair(twin):
    """Rows at different positions fed one chunk by the scan (seq 1
    stepped CHUNK times) and by the pass from the SAME pool, then one
    decode step each: (rows' tokens, tables, state before, {program:
    (state after the chunk, the decode step's logits)})."""
    ffd, run, btab = twin
    starts = np.array(list(STARTS.values()), np.int32)
    table = btab.copy()
    table[list(STARTS).index("rider")] = 0
    tokens = np.random.default_rng(17).integers(
        1, D.v, (SLOTS, int(starts.max()) + CHUNK + 1)).astype(np.int32)
    state = ffd._state
    for t in range(int(starts.max())):  # each row's history, a token a step
        live = starts > t
        _, state = run("step", state, np.where(live, tokens[:, t], 0),
                       np.where(live, t, 0), np.where(live[:, None], table, 0))
    cols = starts[:, None] + np.arange(CHUNK + 1)
    feed = np.take_along_axis(tokens, cols, axis=1)
    after = {}
    for name in ("scan", "pass"):
        st = run(name, state, feed[:, :CHUNK], starts, table)
        logits, _ = run("step", st, feed[:, CHUNK], starts + CHUNK, table)
        after[name] = (st, np.asarray(logits, np.float32))
    return tokens, table, state, after


@pytest.mark.parametrize("row", [r for r in STARTS if r != "rider"])
def test_chunk_in_one_pass_equals_seq1_stepped_over_the_chunk(chunk_pair, row):
    """By tolerance, not bytes: the latent pool's live entries of the
    row, the next decode step's logits, and those logits against the
    reference's full forward of the row's tokens."""
    tokens, table, _, after = chunk_pair
    i, end = list(STARTS).index(row), STARTS[row] + CHUNK
    blocks = table[i, :-(-end // PAGE)]
    scan, one = _pools(after["scan"][0]), _pools(after["pass"][0])
    assert len(one) == D.L
    for op in one:
        close(one[op][blocks], scan[op][blocks], OP_TOL)
    close(after["pass"][1][i], after["scan"][1][i], LOGIT_TOL)
    close(after["pass"][1][i], reference_logits(tokens[i, :end + 1])[end],
          LOGIT_TOL)


def test_a_rider_of_the_pass_writes_scratch_only(chunk_pair):
    """Blocks no fed row wrote stay byte-equal; the fed rows' blocks
    are covered above, scratch (block 0) may hold anything."""
    _, table, before, after = chunk_pair
    wrote = {0} | {int(table[i, c]) for i, row in enumerate(STARTS)
                   for c in range(-(-(STARTS[row] + CHUNK) // PAGE))}
    rest = sorted(set(range(before["attn_0"]["latent_cache"].shape[0]))
                  - wrote)
    was, now = _pools(before), _pools(after["pass"][0])
    for op in now:
        assert np.array_equal(now[op][rest], was[op][rest]), op


def test_pass_keeps_the_pad_contract_at_the_end_of_the_position_range(twin):
    """A row within CHUNK of max_seq: its positions >= max_seq go to
    scratch, so every other block is byte-unchanged outside the row's
    own frontier, and the last in-range position holds the token fed
    THERE, not a later pad clamped onto it."""
    ffd, run, btab = twin
    rng = np.random.default_rng(23)
    state = {op: {k: (jnp.asarray(rng.standard_normal(v.shape), v.dtype)
                      if k == "latent_cache" else v)
                  for k, v in e.items()} for op, e in ffd._state.items()}
    starts = np.array([D.p - 2, D.p - 3, 0, 0], np.int32)
    table = btab.copy()
    table[2:] = 0
    feed = rng.integers(1, D.v, (SLOTS, CHUNK)).astype(np.int32)
    was = _pools(state)
    scan = _pools(run("scan", state, feed, starts, table))
    one = _pools(run("pass", state, feed, starts, table))
    frontier = np.zeros(was["attn_0"].shape[:2], bool)
    frontier[0] = True                                   # scratch
    for i in (0, 1):
        for pos in range(starts[i], D.p):
            frontier[table[i, pos // PAGE], pos % PAGE] = True
    assert frontier.sum() == PAGE + 2 + 3
    for op in one:
        assert np.array_equal(one[op][~frontier], was[op][~frontier]), op
        frontier[0] = False
        close(one[op][frontier], scan[op][frontier], OP_TOL)
        assert not np.allclose(one[op][frontier], was[op][frontier])
        frontier[0] = True


def test_gpt_scheduler_keeps_the_scanned_prefill_program():
    """GPT's recipe does not carry `prefill_pass`: its scheduler counts
    `prefill_chunk` passes a dispatch and its prefill program is
    `build_paged_prefill_step`'s, lowered to the same text."""
    from flexflow_tpu import LossType, SGDOptimizer
    from flexflow_tpu.decoding import build_paged_prefill_step
    from flexflow_tpu.models.transformer import build_gpt
    from flexflow_tpu.serving.scheduler import ContinuousScheduler

    ff = FFModel(FFConfig(batch_size=1, num_devices=1))
    build_gpt(ff, 1, 16, hidden_size=32, num_layers=2, num_heads=2,
              intermediate_size=64, vocab_size=50)
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=jax.devices()[:1])
    assert "prefill_pass" not in ff.decoder_recipe.carries
    sched = ContinuousScheduler.from_trained(
        ff, batch_slots=2, page_size=4, num_blocks=9, prefill_chunk=4,
        devices=jax.devices()[:1])
    try:
        model = sched.model
        assert sched.stats()["prefill_passes"] == 4 == model.prefill_passes
        fn = model._prefill_fn
        assert "build_paged_prefill_step.<locals>" in fn.__wrapped__.__qualname__
        args = (model.ffd._weights, model._state,
                jnp.zeros((2, 4), jnp.int32), jnp.zeros(2, jnp.int32),
                jnp.zeros((2, 4), jnp.int32))
        assert fn.lower(*args).as_text() == build_paged_prefill_step(
            model.ffd, 4).lower(*args).as_text()
    finally:
        sched.close(10)


# -- 3. the share test -----------------------------------------------------------
def _glm_dsa_share():
    """The same test on `glm_dsa`'s toy (16 experts in shares of 4):
    its reference walks blocks of a padded sequence."""
    from benchmarks.families import glm_dsa as glm

    cfg = config("toy-glm52.json")

    def experts(row, key, layer, d, q, held):
        rows = jnp.zeros((d.p, d.e)).at[:len(row)].set(row)
        return tuple(part[:len(row)] for part in glm.experts(
            rows, len(row), key, layer, d, q, held=held))

    return glm, cfg, experts


SHARES = {"kimi_k2": lambda: (fam, CFG, fam.experts),
          "glm_dsa": _glm_dsa_share}


@pytest.mark.parametrize("family", sorted(SHARES))
def test_every_share_of_an_expert_layer_adds_up_to_the_uncut_layer(family):
    """The routed parts that all `total / held` shares give, with the
    shared expert counted once, are the uncut reference's whole layer:
    through the PROGRAM's op for each share, against the reference
    given every expert."""
    fam, CFG, experts = SHARES[family]()
    D = fam.dims(CFG)
    x = np.asarray(jax.random.normal(jax.random.key(7), (2, 12, D.e)))
    pos = np.zeros((2, 12), np.int32)
    with jax.default_matmul_precision("highest"):
        whole = np.stack([sum(experts(
            jnp.asarray(row), KEY, 1, D, lambda v: v, held=(0, D.total)))
            for row in x])
        shared = np.stack([experts(
            jnp.asarray(row), KEY, 1, D, lambda v: v, held=(0, 0))[1]
            for row in x])
    total = np.zeros_like(whole)
    for first in range(0, D.total, D.held):
        cfg = dict(CFG, deployment=dict(CFG["deployment"],
                                        first_held_expert=first))
        d = fam.dims(cfg)
        params = dict(fam.published(cfg))
        ff, name = one_op_model(lambda ff, x, pos: ff.routed_experts(
            x, _experts_params(params), name="op"))
        ff.set_weights({name: jax.tree.map(np.asarray, fam.make_op(
            KEY, 1, d=d, kind="moe", dtype=jnp.dtype("float32")))})
        total += np.asarray(ff.forward({"x": x, "positions": pos})) - shared
    close(total + shared, whole, OP_TOL)


def _experts_params(kw):
    from flexflow_tpu.ops.routed_experts import RoutedExpertsParams

    return RoutedExpertsParams(
        experts_total=kw["n_routed_experts_total"],
        experts_held=kw["n_routed_experts"],
        first_held=kw["first_held_expert"], top_k=kw["num_experts_per_tok"],
        expert_hidden=kw["moe_intermediate_size"],
        shared_hidden=kw["n_shared_experts"] * kw["moe_intermediate_size"],
        routed_scaling_factor=kw["routed_scaling_factor"])


# -- 4. no pair is dropped ---------------------------------------------------------
def test_no_pair_is_dropped_when_every_token_chooses_one_expert():
    from flexflow_tpu.ops.routed_experts import MOE_STATS

    ff, name = one_op_model(lambda ff, x, pos: ff.routed_experts(
        x, _experts_params(fam.published(CFG)), name="op"))
    w = jax.tree.map(np.asarray, fam.make_op(
        KEY, 1, d=D, kind="moe", dtype=jnp.dtype("float32")))
    w["router_bias"] = w["router_bias"].copy()
    w["router_bias"][D.first_held + 1] = 100.0  # everyone's first choice
    ff.set_weights({name: w})
    x = jax.random.normal(jax.random.key(9), (2, 12, D.e))
    _, state, _, _ = ff.executor.run_forward(
        ff._weights, ff._state,
        {"x": x, "positions": jnp.zeros((2, 12), jnp.int32)},
        training=False, rng=None)
    stats = dict(zip(MOE_STATS, np.asarray(state[name]["moe_stats"])))
    assert stats["dropped"] == 0
    assert stats["max_rows"] == 24           # all 24 rows on that expert
    assert stats["pairs"] >= 24 and 1 <= stats["hit"] <= D.held


# -- 5. closed forms, and the router's precision -------------------------------------
def test_yarn_frequencies_and_softmax_scale_against_the_closed_forms():
    """ISSUE 29's closed forms at the PUBLISHED values."""
    from flexflow_tpu.ops.mla import (MLAParams, softmax_scale,
                                      yarn_frequencies)

    p = MLAParams(embed_dim=7168, num_heads=64, q_lora_rank=1536,
                  kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, rope_theta=50000.0,
                  rope_factor=64.0, rope_original_max=4096, beta_fast=32,
                  beta_slow=1, mscale=1.0, mscale_all_dim=1.0)
    d, theta = 64, 50000.0
    i = np.arange(32)
    extra = theta ** (-2.0 * i / d)
    dim = lambda r: d * np.log(4096 / (2 * np.pi * r)) / (2 * np.log(theta))  # noqa: E731
    low, high = max(np.floor(dim(32)), 0), min(np.ceil(dim(1)), d - 1)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    np.testing.assert_allclose(
        yarn_frequencies(p), extra / 64 * ramp + extra * (1 - ramp),
        rtol=1e-12)
    m = 0.1 * 1.0 * np.log(64) + 1
    assert abs(m - 1.4159) < 1e-4
    assert abs(softmax_scale(p) - 192 ** -0.5 * m * m) < 1e-12
    # the reference's own copy of the same forms agrees at the toy size
    np.testing.assert_allclose(
        yarn_frequencies(mla_params()), fam.yarn_frequencies(D), rtol=1e-12)
    assert abs(softmax_scale(mla_params()) - fam.softmax_scale(D)) < 1e-12


def test_yarn_frequencies_are_bit_equal_after_their_move_to_ops_rope():
    """PR 55 moved the YaRN blend to `ops/rope.py`, a function of
    (rotary dim, theta, factor, original, beta_fast, beta_slow) that
    `ops/mla.py` and `ops/attention.py` both call: this family's
    frequencies are the bytes they were (the expression of PR 29,
    written out here), and plain RoPE is the factor-1 case."""
    import math

    from flexflow_tpu.ops.mla import MLAParams, yarn_frequencies
    from flexflow_tpu.ops.rope import yarn_frequencies as shared

    def as_it_was(d, theta, factor, original, beta_fast, beta_slow):
        extra = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
        if factor <= 1:
            return extra

        def pair_of(turns):
            return (d * math.log(original / (2 * math.pi * turns))
                    / (2 * math.log(theta)))

        low = max(math.floor(pair_of(beta_fast)), 0)
        high = min(math.ceil(pair_of(beta_slow)), d - 1)
        ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                       / max(high - low, 1e-3), 0.0, 1.0)
        return extra / factor * ramp + extra * (1.0 - ramp)

    published = (64, 50000.0, 64.0, 4096, 32.0, 1.0)
    p = MLAParams(embed_dim=7168, num_heads=64, q_lora_rank=1536,
                  kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, rope_theta=50000.0,
                  rope_factor=64.0, rope_original_max=4096, beta_fast=32,
                  beta_slow=1)
    for got in (yarn_frequencies(p), shared(*published)):
        assert got.dtype == np.float64
        np.testing.assert_array_equal(got, as_it_was(*published))
    np.testing.assert_array_equal(yarn_frequencies(mla_params()), as_it_was(
        D.dr, D.theta, *(float(D.rope[k]) for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow"))))
    # Laguna's full layers: 32 pairs of a 64-channel rotary half
    laguna = (64, 500000.0, 64.0, 4096, 64.0, 1.0)
    np.testing.assert_array_equal(shared(*laguna), as_it_was(*laguna))
    np.testing.assert_array_equal(
        shared(128, 10000.0),
        10000.0 ** (-np.arange(0, 128, 2, dtype=np.float64) / 128))


def test_router_chooses_in_float32_when_compute_dtype_is_bfloat16():
    """The executor hands the router's weights over in float32, and the
    experts chosen for bf16-computed activations are the float32
    top-k of those activations, where a bf16 product picks others."""
    from flexflow_tpu.ops.routed_experts import route

    ff = holder(precision="bfloat16")
    assert ff._weights["moe_1"]["router"].dtype == jnp.float32
    assert ff._weights["moe_1"]["w_gate"].dtype == jnp.bfloat16
    op = next(o for o in ff.operators.topo_order() if o.name == "moe_1")
    seen = {}
    inner = op.forward

    def spy(inputs, weights, **kw):
        seen["dtypes"] = [w.dtype for w in weights]
        return inner(inputs, weights, **kw)

    op.forward = spy
    ids = np.arange(1, 13, dtype=np.int32)[None]
    ff.forward({"input": np.pad(ids, ((0, 0), (0, D.p - 12))),
                "positions": np.arange(D.p, dtype=np.int32)[None]})
    assert seen["dtypes"][:2] == [jnp.float32, jnp.float32]  # router, bias
    assert seen["dtypes"][2] == jnp.bfloat16
    # a wide router (the published 384 outputs, top 8) over bf16
    # activations: the float32 choice differs from a bf16 product's
    h = jax.random.normal(jax.random.key(1), (64, 256)).astype(jnp.bfloat16)
    r = 0.02 * jax.random.normal(jax.random.key(2), (256, 384))
    p = _experts_params(dict(fam.published(CFG), n_routed_experts_total=384,
                             num_experts_per_tok=8))
    chosen, _ = route(h, r, jnp.zeros(384), p)
    want = jax.lax.top_k(jax.nn.sigmoid(jnp.matmul(
        h.astype(jnp.float32), r, precision="highest")), 8)[1]
    low = jax.lax.top_k(jax.nn.sigmoid(jnp.matmul(
        h, r.astype(jnp.bfloat16)).astype(jnp.float32)), 8)[1]
    assert np.array_equal(np.sort(chosen), np.sort(want))
    assert not np.array_equal(np.sort(low), np.sort(want))


# -- 6. the comparison tells the precisions apart --------------------------------------
def test_bf16_logits_leave_the_float32_tolerance():
    tokens = np.random.default_rng(3).integers(1, D.v, D.p)
    inputs = {"input": tokens[None].astype(np.int32),
              "positions": np.arange(D.p, dtype=np.int32)[None]}
    want = reference_logits(tokens)
    close(holder().forward(inputs)[0], want, LOGIT_TOL)
    got = np.asarray(holder(precision="bfloat16").forward(inputs)[0],
                     np.float32)
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err > 100 * LOGIT_TOL, err


# -- 7. what the family does not carry ---------------------------------------------------
def _front(**ffconfig):
    from flexflow_tpu.serving import build_front

    return build_front(holder(**ffconfig))


def _beam():
    from flexflow_tpu.decoding import gpt_beam_search_cached, make_decoder

    twin = make_decoder(holder(), batch_size=2, kv_page_size=4,
                        kv_num_blocks=20, devices=jax.devices()[:1])
    gpt_beam_search_cached(twin, [[1, 2, 3]], 2, beam_size=2)


def _dense_cache():
    from flexflow_tpu.decoding import make_decoder

    make_decoder(holder(), batch_size=2, devices=jax.devices()[:1])


def _kernel_by_name():
    from flexflow_tpu.serving.scheduler import PagedKVDecodeModel

    PagedKVDecodeModel(holder(), batch_slots=2, page_size=4,
                       paged_kernel="pallas", devices=jax.devices()[:1])


NOT_CARRIED = {
    "speculative": lambda: _front(spec_decode="ngram"),
    "tensor_parallel": lambda: _front(serving_tp=2),
    "disaggregated": lambda: _front(serving_roles="prefill=1,decode=1"),
    "handoff": lambda: _front(serving_handoff=True),
    "beam_search": _beam,
    "dense_cache": _dense_cache,
    "pallas_read": _kernel_by_name,
}


@pytest.mark.parametrize("feature", sorted(NOT_CARRIED))
def test_feature_not_carried_is_a_config_error_by_name(feature):
    with pytest.raises(ConfigError, match=f"kimi_k2 does not carry {feature}"):
        NOT_CARRIED[feature]()


# -- the served model holds its weights once ---------------------------------------------
def test_deferred_weights_are_held_once_in_the_precision_given():
    from flexflow_tpu.decoding import make_decoder

    ff = holder(precision="bfloat16")
    assert ff._opt_state is None and ff._step_fn is None
    twin = make_decoder(ff, batch_size=2, kv_page_size=4, kv_num_blocks=20,
                        devices=jax.devices()[:1])
    for op, entries in twin._weights.items():
        for k, v in entries.items():
            assert v is ff._weights[op][k], (op, k)  # no second copy
    dtypes = {str(v.dtype) for e in twin._weights.values()
              for v in e.values()}
    assert dtypes == {"bfloat16", "float32"}
    floats = sum(v.size for e in twin._weights.values() for v in e.values()
                 if v.dtype == jnp.float32)
    assert floats == 2 * (D.e * D.total + D.total)  # the routers only
    assert twin._state["attn_0"]["latent_cache"].dtype == jnp.bfloat16
    with pytest.raises(RuntimeError, match="defer_weights"):
        ff.train_step({}, np.zeros(1))


def test_gpt_twin_state_and_pool_predicate_are_unchanged():
    """`cache_entries` names the GPT-2 twin's k/v pools; its state
    pytree is what it was."""
    from flexflow_tpu import LossType, SGDOptimizer
    from flexflow_tpu.decoding import cache_entries, make_decoder
    from flexflow_tpu.models.transformer import build_gpt

    ff = FFModel(FFConfig(batch_size=1, num_devices=1))
    build_gpt(ff, 1, 16, hidden_size=32, num_layers=2, num_heads=2,
              intermediate_size=64, vocab_size=50)
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=jax.devices()[:1])
    twin = make_decoder(ff, batch_size=2, kv_page_size=4, kv_num_blocks=9,
                        devices=jax.devices()[:1])
    assert cache_entries(twin) == {"attn_0": ("k_cache", "v_cache"),
                                   "attn_1": ("k_cache", "v_cache")}
    assert {op: sorted(e) for op, e in twin._state.items()} == {
        f"attn_{i}": ["block_table", "k_cache", "seq_lens", "v_cache"]
        for i in range(2)}
    assert twin._state["attn_0"]["k_cache"].shape == (9, 4, 2, 16)
