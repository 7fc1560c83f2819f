"""The longcat_flash language model (a double layer of two latent
attentions and two dense MLPs with the routed experts on a shortcut
across it; identity experts in a softmax router; constant scales on the
latent bottlenecks) against its plain float32 reference
(benchmarks/families/longcat_flash.py) on seeded weights, at a toy size
on the CPU, comparing LOGITS.

Tolerances as tests/test_kimi_k2.py's: the program and the reference
compute the same float32 arithmetic in another order, so they differ by
rounding only: 1e-5 of the compared tensor's largest magnitude for one
op, 2e-5 for logits that went through every layer.  A reference whose
shortcut joins one sublayer early is another model and misses that by
more than ten times at every position
(`test_shortcut_joined_one_sublayer_early_fails_the_same_tolerance`).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _family import (Recorder, close, config, op_alone, padded,
                     reference_side)

from benchmarks import reference as ref
from benchmarks.families import longcat_flash as fam
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.config import ConfigError
from flexflow_tpu.models.longcat_flash import build_longcat_flash
from flexflow_tpu.ops import routed_experts as rx
from flexflow_tpu.ops.mla import MLAParams
from flexflow_tpu.ops.routed_experts import (MOE_STATS, MOE_ZERO_STATS,
                                             RoutedExpertsParams, route)

CFG = config("toy-longcat-flash.json")
D = fam.dims(CFG)
SEED = 11
KEY = ref.seed_key(SEED)
OP_TOL, LOGIT_TOL = 1e-5, 2e-5


def holder(cfg=CFG, seq=None, **ffconfig):
    """The served model's holder with the seed's weights set."""
    dep = cfg["deployment"]
    ff = FFModel(FFConfig(
        batch_size=1, num_devices=1, compute_dtype=cfg["precision"],
        serving_slots=dep["serving_slots"], kv_page_size=dep["kv_page_size"],
        kv_pool_blocks=dep["kv_pool_blocks"], **ffconfig))
    build_longcat_flash(ff, 1, seq or cfg["n_positions"],
                        **fam.published(cfg))
    ff.compile(devices=jax.devices()[:1], defer_weights=True)
    ff.set_weights(fam.make_weights(cfg, SEED, "program"))
    return ff


def reference_logits(tokens, **kw):
    return np.asarray(fam.logits_fn(
        fam.make_weights(CFG, SEED, "reference"), padded(tokens),
        "float32", **kw))[:len(tokens)]


def graph_op(name, cfg=CFG):
    ff = FFModel(FFConfig(batch_size=1, num_devices=1))
    build_longcat_flash(ff, 1, 8, **fam.published(cfg))
    return next(op for op in ff.layers.topo_order() if op.name == name)


def experts_params(**changes) -> RoutedExpertsParams:
    return dataclasses.replace(graph_op("moe_0").params, **changes)


# -- 1. identity experts in the router and in both products -----------------------
def routed_reference(a, w, d=D):
    """The family's equations over given weights: a [s, e] -> [s, e]."""
    q = lambda v: v  # noqa: E731
    weights = fam.routing(a, w["router"], w["router_bias"], d)
    out = sum(weights[:, d.first_held + x, None]
              * fam.gated(a, w["w_gate"][x], w["w_up"][x], w["w_down"][x], q)
              for x in range(d.held))
    return out + jnp.sum(weights[:, d.total:], axis=-1, keepdims=True) * a


EXPERT_LEAVES = {"router": (D.e, D.width), "router_bias": (D.width,),
                 "w_gate": (D.held, D.e, D.f), "w_up": (D.held, D.e, D.f),
                 "w_down": (D.held, D.f, D.e)}


@pytest.fixture(scope="module")
def routed_case():
    return reference_side(routed_reference, EXPERT_LEAVES, D.e)


@pytest.mark.parametrize("plan", ["dense", "grouped"])
def test_identity_experts_match_the_reference_forward_and_gradient(
        plan, routed_case, monkeypatch):
    monkeypatch.setattr(rx, "GROUPED_MIN_ROWS_PER_EXPERT",
                        1 if plan == "grouped" else 10 ** 9)
    op = op_alone(lambda ff, x, _: ff.routed_experts(
        x, experts_params(), name="op"), routed_case,
        chooses=("router_bias",))
    assert op.product_plan() == plan
    assert [s.name for s in op.weight_specs[5:]] == (
        ["moe_stats"] + ["moe_rows_computed"] * (plan == "grouped")
        + ["moe_zero"])


def test_router_is_as_wide_as_the_real_and_the_identity_experts():
    op = graph_op("moe_0")
    p = op.params
    assert (p.experts_total, p.zero_experts, p.router_width) == (
        D.total, D.zero, D.width)
    assert p.scoring == "softmax" and p.norm_topk_prob is False
    shapes = {s.name: s.shape.logical_shape for s in op.weight_specs}
    assert shapes["router"] == (D.e, D.width)
    assert shapes["router_bias"] == (D.width,)
    assert shapes["w_gate"] == (D.held, D.e, D.f)
    # the pairs an even router sends here are counted over that width
    assert rx.pick_expert_product(128 * D.width // D.k, D.held,
                                  p.router_width, p.top_k) == "grouped"
    assert rx.pick_expert_product(128 * D.total // D.k, D.held,
                                  p.router_width, p.top_k) == "dense"
    with pytest.raises(rx.ShapeError, match="identity experts"):
        ff = FFModel(FFConfig(batch_size=1, num_devices=1))
        ff.routed_experts(ff.create_tensor([1, 4, D.e], name="x"),
                          experts_params(top_k=D.width + 1), name="op")


def test_softmax_scores_are_not_renormalised_and_the_bias_only_chooses():
    """`norm_topk_prob=False` with `scoring="softmax"`: a chosen
    output's weight is `scaling` times its softmax score over the WHOLE
    width, whatever else was chosen and whatever the bias."""
    keys = jax.random.split(jax.random.key(2), 3)
    h = jax.random.normal(keys[0], (9, D.e))
    router = 0.3 * jax.random.normal(keys[1], (D.e, D.width))
    bias = jax.random.normal(keys[2], (D.width,))
    p = experts_params()
    chosen, w = route(h, router, bias, p)
    scores = jax.nn.softmax(jnp.matmul(h, router, precision="highest"), -1)
    want = jax.lax.top_k(scores + bias, p.top_k)[1]
    assert np.array_equal(np.sort(chosen), np.sort(want))
    close(w, p.routed_scaling_factor
          * jnp.take_along_axis(scores, chosen, axis=-1))
    assert float(jnp.max(jnp.sum(w, -1))) < p.routed_scaling_factor
    _, renormed = route(h, router, bias,
                        dataclasses.replace(p, norm_topk_prob=True))
    close(jnp.sum(renormed, -1), jnp.full(9, p.routed_scaling_factor))


def layer_outputs(params, w, x, plan, monkeypatch):
    """(out, {state entry: counters}) of one layer's forward."""
    monkeypatch.setattr(rx, "GROUPED_MIN_ROWS_PER_EXPERT",
                        1 if plan == "grouped" else 10 ** 9)
    ff = FFModel(FFConfig(batch_size=x.shape[0], num_devices=1))
    op = ff.routed_experts(ff.create_tensor(list(x.shape), name="x"),
                           params, name="op").owner_op
    assert op.product_plan() == plan
    names = [s.name for s in op.weight_specs]
    state = [jnp.zeros(s.shape.logical_shape, jnp.int32)
             for s in op.weight_specs[5:]]
    out, *counters = jax.jit(lambda x, w: op.forward(
        [x], [w[n] for n in names[:5]] + state))(x, w)
    return np.asarray(out), dict(zip(names[5:], map(np.asarray, counters)))


def seeded_layer(bias=None):
    keys = jax.random.split(jax.random.key(SEED), 5)
    w = {n: 0.3 * jax.random.normal(k, shape)
         for k, (n, shape) in zip(keys, EXPERT_LEAVES.items())}
    w["router_bias"] = jnp.zeros((D.width,)) if bias is None else bias
    return w, jax.random.normal(keys[-1], (2, 12, D.e))


@pytest.mark.parametrize("plan", ["dense", "grouped"])
@pytest.mark.parametrize("picks", ["all_identity", "no_identity"])
def test_rows_whose_picks_are_all_or_none_identity(picks, plan, monkeypatch):
    """A bias of +-100 on the identity outputs: with 8 of them and 6
    picks every row picks identity experts only (its output is its own
    row times the picks' weights, no expert is hit), or none."""
    sign = 100.0 if picks == "all_identity" else -100.0
    w, x = seeded_layer(jnp.zeros((D.width,)).at[D.total:].set(sign))
    out, counters = layer_outputs(experts_params(), w, x, plan, monkeypatch)
    with jax.default_matmul_precision("highest"):
        want = np.stack([routed_reference(row, w) for row in x])
    close(out, want)
    stats = dict(zip(MOE_STATS, counters["moe_stats"]))
    zero = dict(zip(MOE_ZERO_STATS, counters["moe_zero"]))
    rows = x.shape[0] * x.shape[1]
    assert stats["dropped"] == 0
    if picks == "all_identity":
        assert zero == {"zero_picks": rows * D.k, "real_min": 0,
                        "real_max": 0}
        assert stats["pairs"] == stats["hit"] == 0
        scores = jax.nn.softmax(jnp.matmul(
            x, w["router"], precision="highest"), -1)[..., D.total:]
        close(out, x * D.scaling * jnp.sum(
            jax.lax.top_k(scores, D.k)[0], -1, keepdims=True))
    else:
        assert zero == {"zero_picks": 0, "real_min": D.k, "real_max": D.k}
        assert stats["pairs"] > 0


@pytest.mark.parametrize("plan", ["dense", "grouped"])
def test_zero_identity_experts_is_todays_layer_bit_for_bit(plan, monkeypatch):
    """`zero_experts=0` is the default and names today's layer: no
    `moe_zero` entry, the router `experts_total` wide, no new branch
    taken.  And the new branches leave what they do not own alone: a
    layer whose last outputs are IDENTITY experts that no row chooses
    gives, byte for byte, what today's layer gives where the same
    outputs are experts on other chips that no row chooses (one router
    product, the same choice, and the identity term an exact zero)."""
    w, x = seeded_layer(jnp.zeros((D.width,)).at[D.total:].set(-100.0))
    today = RoutedExpertsParams(
        experts_total=D.width, experts_held=D.held, first_held=D.first_held,
        top_k=4, expert_hidden=D.f, routed_scaling_factor=D.scaling)
    assert today.zero_experts == 0 and today.router_width == D.width
    out, counters = layer_outputs(today, w, x, plan, monkeypatch)
    assert sorted(counters) == sorted(
        ["moe_stats"] + ["moe_rows_computed"] * (plan == "grouped"))
    wide, zero = layer_outputs(
        dataclasses.replace(today, experts_total=D.total,
                            zero_experts=D.zero), w, x, plan, monkeypatch)
    assert np.array_equal(wide, out)
    assert np.array_equal(zero["moe_stats"], counters["moe_stats"])
    assert list(zero["moe_zero"]) == [0, 4, 4]


# -- 2. the share test ---------------------------------------------------------
def test_every_share_of_an_expert_layer_adds_up_to_the_uncut_layer():
    """The routed parts that all `total / held` shares give, with the
    identity experts' term (which every chip computes for its own rows)
    counted once, are the uncut reference's whole layer: through the
    PROGRAM's op for each share, against the reference given every
    expert."""
    x = np.asarray(jax.random.normal(jax.random.key(7), (2, 12, D.e)))
    q = lambda v: v  # noqa: E731
    with jax.default_matmul_precision("highest"):
        whole = np.stack([sum(fam.experts(
            jnp.asarray(row), KEY, 1, D, q, held=(0, D.total)))
            for row in x])
        identity = np.stack([fam.experts(
            jnp.asarray(row), KEY, 1, D, q, held=(0, 0))[1] for row in x])
    assert float(np.max(np.abs(identity))) > 0
    total = np.zeros_like(whole)
    for first in range(0, D.total, D.held):
        cfg = dict(CFG, deployment=dict(CFG["deployment"],
                                        first_held_expert=first))
        d = fam.dims(cfg)
        ff = FFModel(FFConfig(batch_size=2, num_devices=1))
        out = ff.routed_experts(ff.create_tensor([2, 12, D.e], name="x"),
                                graph_op("moe_1", cfg).params, name="op")
        ff.compile(devices=jax.devices()[:1], defer_weights=True)
        ff.set_weights({out.owner_op.name: jax.tree.map(
            np.asarray, fam.make_program_op(KEY, d, "moe", 1,
                                            jnp.dtype("float32")))})
        total += np.asarray(ff.forward({"x": x})) - identity
    close(total + identity, whole, OP_TOL)


# -- 3. the two constants of the latent attention ---------------------------------
def mla_params(**changes) -> MLAParams:
    return dataclasses.replace(graph_op("attn_0_0").params, **changes)


def test_builder_sets_the_published_constants():
    p = mla_params()
    assert p.q_lora_scale == pytest.approx((D.e / D.rq) ** 0.5) == D.s_q
    assert p.kv_lora_scale == pytest.approx((D.e / D.rk) ** 0.5) == D.s_kv
    assert p.rope_factor == 1.0 and p.rope_theta == 1e7
    off = graph_op("attn_0_0", dict(CFG, mla_scale_q_lora=False,
                                    mla_scale_kv_lora=False)).params
    assert (off.q_lora_scale, off.kv_lora_scale) == (1.0, 1.0)


def test_expanded_attention_with_the_constants_matches_the_reference():
    op_alone(lambda ff, x, pos: ff.mla_attention(x, pos, mla_params(),
                                                 name="op"),
             reference_side(lambda a, w: fam.attention(a, w, D, lambda v: v),
                            fam.leaf_shapes(D, "attn"), D.e),
             positions=True)


def test_constants_of_one_trace_the_program_without_them():
    """1.0 and 1.0 (every other family's block) hand the norms' gains
    over as they are: the same jaxpr as before the constants existed,
    which a constant other than 1 changes."""
    def jaxpr(p):
        ff = FFModel(FFConfig(batch_size=2, num_devices=1))
        op = ff.mla_attention(
            ff.create_tensor([2, 8, D.e], name="x"),
            ff.create_tensor([2, 8], dtype="int32", name="positions"), p,
            name="op").owner_op
        w = [jnp.ones(s.shape.logical_shape) for s in op.weight_specs]
        return str(jax.make_jaxpr(lambda x, pos: op.forward([x, pos], w))(
            jnp.ones((2, 8, D.e)), jnp.zeros((2, 8), jnp.int32)))

    plain = mla_params(q_lora_scale=1.0, kv_lora_scale=1.0)
    fields = {f.name: getattr(plain, f.name)
              for f in dataclasses.fields(plain)
              if f.name not in ("q_lora_scale", "kv_lora_scale")}
    assert jaxpr(plain) == jaxpr(MLAParams(**fields))
    assert jaxpr(plain).count(" mul ") + 2 == jaxpr(mla_params()).count(
        " mul ")
    with pytest.raises(Exception, match="q_lora_scale without"):
        jaxpr(mla_params(q_lora_rank=0))


# -- 4. served: chunked prefill, then decode, through two planes a layer -----------
@pytest.fixture(scope="module")
def served():
    """One scheduler over the toy model: prompts of whole chunks and of
    chunks and a remainder, a full-prompt prefix hit (copy-on-write), a
    partial hit, three rows at once, a slot reused:
    (recorded rows with the dispatch's routed-expert counts, handles,
    stats, the twin's state shapes)."""
    from flexflow_tpu.serving.scheduler import ContinuousScheduler

    sched = ContinuousScheduler.from_trained(
        holder(), batch_slots=3, page_size=4, num_blocks=40,
        prefill_chunk=4, prefix_cache=True, devices=jax.devices()[:1])
    # (`also` records the decode dispatches' counts; a pass's are on its
    # span and in stats()["moe"] by program: tests/test_pass_decode.py)
    rec = Recorder(sched, also=lambda model, i: (dict(model.moe_last),),
                   pass_also=lambda model, i: (None,))
    try:
        rng = np.random.default_rng(5)
        a = rng.integers(1, D.v, 16).tolist()  # four full pages
        b = a[:8] + rng.integers(1, D.v, 7).tolist()
        c = rng.integers(1, D.v, 9).tolist()
        handles = [sched.generate_async(a, 6, 0.0)]
        handles[0].wait(120)
        handles += [sched.generate_async(p, 5, 0.0) for p in (a, b, c)]
        for h in handles[1:]:
            h.wait(120)
        handles.append(sched.generate_async(c[:5], 4, 0.0))
        handles[-1].wait(120)
        stats = sched.stats()
        shapes = {op: {k: v.shape for k, v in e.items()}
                  for op, e in sched.model._state.items()}
        block_bytes = sched.model.kv_block_bytes
    finally:
        sched.close(10)
    return rec.rows, handles, stats, shapes, block_bytes


def test_served_logits_equal_the_reference_full_forward(served):
    rows, handles, *_ = served
    want = {id(h): reference_logits(h.result) for h in handles}
    assert len(rows) >= 25
    for req, pos, logits, _ in rows:
        close(logits, want[id(req)][pos], LOGIT_TOL)


def test_shortcut_joined_one_sublayer_early_fails_the_same_tolerance(served):
    """The control: a reference that adds the experts' output before the
    second MLP reads the stream is held to the served logits by the
    same tolerance, and misses it."""
    rows, handles, *_ = served
    moved = reference_logits(handles[0].result, join_early=True)
    mine = [(pos, logits) for req, pos, logits, _ in rows
            if req is rows[0][0]]
    assert mine
    for pos, logits in mine:
        err = np.max(np.abs(logits - moved[pos])) / np.max(np.abs(moved[pos]))
        assert err > 10 * LOGIT_TOL, err


def test_twin_holds_two_latent_planes_a_layer_under_one_block_table(served):
    *_, stats, shapes, block_bytes = served
    pools = {op: e["latent_cache"] for op, e in shapes.items()
             if "latent_cache" in e}
    assert sorted(pools) == [f"attn_{i}_{j}" for i in range(D.L)
                             for j in range(2)]
    assert set(pools.values()) == {(40, 4, D.rk + D.dr)}
    assert {e["block_table"] for op, e in shapes.items() if op in pools} \
        == {(3, D.p // 4)}
    # a block of the sequence's one table is a page in every plane
    assert block_bytes == 2 * D.L * 4 * (D.rk + D.dr) * 4
    cfg = dict(CFG, deployment=dict(CFG["deployment"], kv_page_size=4))
    assert fam.latent_block_bytes(cfg) == block_bytes
    assert stats["prefill_passes"] == 1 and stats["prefill_steps"] > 0
    assert stats["prefix_cache"]["cow_copies"] >= 1
    assert stats["requests_done"] == 5


def test_decode_dispatches_count_the_identity_picks(served):
    rows, _, stats, *_ = served
    picks = 3 * D.k * D.L  # every slot's row, every routed layer
    rows = [r for r in rows if r[-1] is not None]  # the decode dispatches'
    assert rows
    for *_, moe in rows:
        assert 0 <= moe["zero_picks"] <= picks
        assert 0 <= moe["real_min"] <= moe["real_max"] <= D.k
        assert moe["dropped"] == 0
    total = stats["moe"]
    assert total["dispatches"] > 0
    assert 0 < total["zero_picks"] < picks * total["dispatches"]
    assert total["real_min"] == min(m["real_min"] for *_, m in rows)
    assert total["real_max"] == max(m["real_max"] for *_, m in rows)
    for name in ("pairs", "dropped", "max_rows", "hit"):
        assert name in total  # what readers before this family find


def test_front_serves_it_and_the_dispatch_span_carries_the_counters():
    from flexflow_tpu.obs.trace import next_span_id, spans
    from flexflow_tpu.serving import build_front

    front = build_front(holder(prefill_chunk=4))
    try:
        first = next_span_id()
        prompt = list(range(1, 14))
        tokens = front.generate(prompt, 3, 0.0)
        moe = front.stats()["replicas"][0]["moe"]
    finally:
        front.close()
    want = reference_logits(tokens)
    assert tokens[13:] == [int(np.argmax(want[p])) for p in (12, 13, 14)]
    mine = [r for r in spans() if r.span_id > first]
    chunks = [r for r in mine if r.name == "sched.prefill.dispatch"]
    assert [r.args["tokens"] for r in chunks] == [4, 4, 4]
    assert all(r.args["passes"] == 1 for r in chunks)
    decodes = [r for r in mine if r.name == "sched.decode.dispatch"]
    counts = {"moe_pairs", "moe_dropped", "moe_max_rows", "moe_hit",
              "moe_zero_picks", "moe_real_min", "moe_real_max"}
    assert decodes and all(counts <= set(r.args) for r in decodes)
    # the passes count their real tokens, and the front's stats sum the
    # two programs apart
    assert all(counts | {"slots", "decode_rows"} <= set(r.args)
               for r in chunks)
    assert moe["prefill_dispatches"] == 3
    assert moe["dispatches"] == len(decodes)
    assert moe["prefill_pairs"] == sum(r.args["moe_pairs"] for r in chunks)
    assert moe["zero_picks"] == sum(r.args["moe_zero_picks"]
                                    for r in decodes)
    # four real tokens a pass make at most 4 x top_k picks a layer
    assert all(r.args["moe_zero_picks"] <= 4 * D.k * D.L for r in chunks)


# -- 5. what the family counts and does not carry -----------------------------------
def test_counts_of_the_published_share_redo_the_issues_arithmetic():
    d = fam.dims(config("longcat-flash-ep32-serve.json"))
    c = fam.parameter_counts(d)
    assert round(c["one_attention"] / 1e6, 2) == 90.57
    assert c["one_mlp"] == 226_492_416 and c["one_expert"] == 37_748_736
    assert (d.width, d.k, d.held, d.L) == (768, 12, 16, 4)
    # (the issue's 5,172.6 M leaves the norms' 0.16 M gains out)
    assert abs(fam.total_parameters(d) / 1e6 - 5172.6) < 0.2
    cfg = config("longcat-flash-ep32-serve.json")
    assert fam.latent_block_bytes(cfg) == 8 * 16 * 576 * 2
    # a prefill pass's floors: the pairs routing asks for, below what
    # the dense product runs, and no more bytes than the weights held
    dense_rows = 2.0 * 256 * (3 * d.held * c["one_expert"])
    asked = fam.prefill_pass_flops(cfg, 256, 0) \
        - fam.prefill_pass_flops(dict(cfg, moe_topk=0), 256, 0)
    assert asked == pytest.approx(2.0 * 3 * 256 * 12 * 16 / 768
                                  * c["one_expert"])
    assert asked < dense_rows / 10
    assert fam.prefill_pass_bytes(cfg, 256) < 2 * fam.total_parameters(d)
    assert fam.prefill_pass_bytes(cfg, 1) < fam.prefill_pass_bytes(cfg, 256)


@pytest.mark.parametrize("feature", ["speculative", "tensor_parallel"])
def test_feature_not_carried_is_a_config_error_by_name(feature):
    from flexflow_tpu.serving import build_front

    kw = {"speculative": dict(spec_decode="ngram"),
          "tensor_parallel": dict(serving_tp=2)}[feature]
    with pytest.raises(ConfigError,
                       match=f"longcat_flash does not carry {feature}"):
        build_front(holder(**kw))
