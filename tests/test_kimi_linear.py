"""The kimi_linear language model (Kimi Delta Attention: the delta rule
with a decay per channel, run a chunk at a time; a latent-attention
layer without positions and without a query bottleneck; dense then
sigmoid-routed experts beside a shared one) against its plain float32
reference (benchmarks/families/kimi_linear.py, whose recurrence runs a
position at a time) on seeded weights, at a toy size on the CPU:

1. the chunked rule against `delta_rule_scan`: output, final state and
   the gradients of q, k, v, g, beta (the Pallas kernels of the same
   rule, ops/pallas/chunked_delta_rule.py, and the ops that take them:
   tests/test_gated_delta_rule.py; here what the step's span counts);
2. each new op alone, forward and gradient, and the gradient tests
   ROADMAP R0(c) said were missing (the expanded MLA path with its
   bottleneck and rotation, `GatedDeltaNet`'s stateless shape);
3. the whole model's logits and its FIRST-STEP GRADIENT through
   `FFModel.compile` and `train_step`;
4. the share test of the model-configs guide;
5. the flash kernels at keys of 192 (padded to 256) with values of 128,
   in interpret mode.

Tolerances as tests/test_lfm2_moe.py: the same float32 arithmetic in
another order.
"""
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import check
from benchmarks.families import kimi_k2 as fam_k2
from benchmarks.families import kimi_linear as fam
from benchmarks.families import qwen3_next as fam_q3
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.config import ConfigError
from flexflow_tpu.models.kimi_linear import build_kimi_linear, layer_kinds
from flexflow_tpu.obs import trace
from flexflow_tpu.ops import chunked_delta_rule as cdr
from flexflow_tpu.ops import kimi_delta_attention as kda_op
from flexflow_tpu.ops.gated_delta_net import delta_rule_scan, l2norm
from flexflow_tpu.ops.kimi_delta_attention import KimiDeltaAttentionParams
from flexflow_tpu.ops.mla import MLAParams
from flexflow_tpu.ops.pallas import flash_attention as fa
from flexflow_tpu.ops.pallas import gated_delta_rule as gdr
from flexflow_tpu.ops.routed_experts import RoutedExpertsParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name)) as f:
        return json.load(f)


CFG = config("toy-kimi-linear.json")
D = fam.dims(CFG)
SEED = 13
B, S = 2, 16
OP_TOL, LOGIT_TOL, GROUP_TOL = 1e-5, 2e-5, 2e-5


def close(got, want, tol=OP_TOL):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    scale = float(np.max(np.abs(want))) or 1.0
    assert float(np.max(np.abs(got - want))) / scale <= tol


# -- 1. the chunked rule against the scan a position ---------------------------
def recurrence_inputs(s, per_channel, strong, b=2, h=3, dk=8, dv=8):
    keys = jax.random.split(jax.random.key(0), 8)
    g_shape = (b, s, h, dk) if per_channel else (b, s, h)
    return dict(
        S=jax.random.normal(keys[5], (b, h, dk, dv)),
        q=l2norm(jax.random.normal(keys[0], (b, s, h, dk))) * dk ** -0.5,
        k=l2norm(jax.random.normal(keys[1], (b, s, h, dk))),
        v=jax.random.normal(keys[2], (b, s, h, dv)),
        # strong: a chunk's decays sum to hundreds, so the textbook
        # `exp(-sum g)` is inf in float32
        g=-jax.nn.softplus(jax.random.normal(keys[3], g_shape))
        * (40.0 if strong else 1.0),
        beta=jax.nn.sigmoid(jax.random.normal(keys[4], (b, s, h))),
    ), (jax.random.normal(keys[6], (b, s, h, dv)),
        jax.random.normal(keys[7], (b, h, dk, dv)))


@pytest.mark.parametrize("strong", [False, True], ids=["mild", "strong"])
@pytest.mark.parametrize("decay", ["per_channel", "per_head"])
@pytest.mark.parametrize("seq, chunk, sub, flat", [
    (16, 1, 1, False), (16, 4, 4, False), (32, 16, 16, False),  # whole chunks
    # sub-chunks, a ragged last chunk
    (37, 16, 4, False), (37, 8, 4, False),
    (70, 64, 16, False),                   # the cell's chunk and sub-chunk
    # through `CHUNKED_RULES`' signature as `KimiDeltaAttention` calls
    # it: q~, k~ as the convs leave them, flat, the l2norm the rule's
    (32, 16, 16, True), (70, 64, 16, True),
])
def test_chunked_rule_equals_the_scan_forward_and_gradient(
        seq, chunk, sub, flat, decay, strong):
    xs, (probe_o, probe_s) = recurrence_inputs(
        seq, decay == "per_channel", strong)
    by_head = xs["q"].shape
    if flat:  # unit rows no more, and no head axis (o as v comes)
        probe_o = probe_o.reshape(by_head[:2] + (-1,))
        xs.update({n: (3.0 * xs[n] if n in "qk" else xs[n]).reshape(
            by_head[:2] + (-1,)) for n in "qkv" + "g" * (xs["g"].ndim == 4)})
    if strong and chunk > 1:
        total = np.cumsum(np.asarray(xs["g"], np.float64), axis=1)
        with np.errstate(over="ignore"):
            assert np.isinf(np.exp(-total[:, :chunk]).astype(np.float32)).any()

    def scalar(rule):
        def f(args):
            state, o = rule(*(args[n] for n in "S q k v g beta".split()))
            return jnp.sum(o * probe_o) + jnp.sum(state * probe_s), (state, o)
        return f

    def chunked(S, q, k, v, g, beta):
        if flat:  # (one decay a head: repeated, as `GatedDeltaNet` does)
            if decay == "per_head":
                g = jnp.repeat(g, by_head[-1], axis=2)
            return kda_op.CHUNKED_RULES["chunked"](S, q, k, v, g, beta,
                                                   chunk, sub)
        return cdr.delta_rule_chunked(S, q, k, v, g, beta, chunk=chunk,
                                      sub=sub)

    def scanned(S, q, k, v, g, beta):
        if flat:
            q, k, v = (t.reshape(by_head) for t in (q, k, v))
            q, k = l2norm(q) * by_head[-1] ** -0.5, l2norm(k)
            g = g if g.ndim == 3 and decay == "per_head" \
                else g.reshape(by_head)
        S, o = delta_rule_scan(S, q, k, v, g, beta)
        return S, o.reshape(probe_o.shape)

    (_, (s_want, o_want)), g_want = jax.value_and_grad(
        scalar(scanned), has_aux=True)(xs)
    (_, (s_got, o_got)), g_got = jax.value_and_grad(
        scalar(chunked), has_aux=True)(xs)
    close(o_got, o_want)
    close(s_got, s_want)
    for name in ("S", "q", "k", "v", "g", "beta"):
        assert np.all(np.isfinite(np.asarray(g_got[name])))
        close(g_got[name], g_want[name], 1e-4 if name == "g" else 2e-5)


def test_chunked_rule_with_rounded_operands_stays_near_the_scan():
    """bf16 operands for the products with the state (what the chip
    runs): a rounding error, not another function."""
    xs, _ = recurrence_inputs(70, True, False)
    args = [xs[n] for n in "S q k v g beta".split()]
    _, want = delta_rule_scan(*args)
    _, got = cdr.delta_rule_chunked(*args, chunk=64, sub=16,
                                    operand_dtype=jnp.bfloat16)
    close(got, want, 3e-2)


@pytest.mark.parametrize("tokens, want", [
    (8192, (64, 16)), (64, (64, 16)), (63, (64, 16)), (16, (16, 16)),
    (24, (32, 16)), (5, (5, 5)), (1, (1, 1))])
def test_pick_chunk_is_whole_sub_chunks_of_the_step(tokens, want):
    assert cdr.pick_chunk(tokens) == want


# -- 2. each op alone, forward and gradient ------------------------------------
def kda_params(**kw):
    return KimiDeltaAttentionParams(**{**dict(
        embed_dim=D["e"], num_heads=D["kh"], head_dim=D["kd"],
        conv_kernel=D["taps"], eps=D["eps"]), **kw})


def mla_params(**kw):
    return MLAParams(**{**dict(
        embed_dim=D["e"], num_heads=D["heads"], q_lora_rank=0,
        kv_lora_rank=D["rk"], qk_nope_head_dim=D["dn"],
        qk_rope_head_dim=D["dr"], v_head_dim=D["dv"], eps=D["eps"],
        nope=True), **kw})


def op_alone(build, reference, leaves, seq=S, inputs=1, positions=False,
             prepare=None, embed=D["e"], grad_tol=OP_TOL, leaf_tol=()):
    """The op's `forward` against `reference(row [s, embed], {leaf})`,
    output and the gradients of the input and of every leaf (within
    `grad_tol`, but the leaves `leaf_tol` names their own)."""
    ff = FFModel(FFConfig(batch_size=B, num_devices=1))
    x_t = ff.create_tensor([B, seq, embed], name="x")
    pos_t = ff.create_tensor([B, seq], dtype="int32", name="positions") \
        if positions else None
    op = build(ff, x_t, pos_t).owner_op
    if prepare:
        prepare(op)
    names = [spec.name for spec in op.weight_specs]
    assert set(names) == set(leaves)
    keys = jax.random.split(jax.random.key(SEED), len(names) + 2)
    w = {n: 0.3 * jax.random.normal(k, leaves[n])
         + (1.0 if "norm" in n else 0.0) for k, n in zip(keys, names)}
    x = jax.random.normal(keys[-1], (B, seq, embed))
    probe = jax.random.normal(keys[-2], (B, seq, embed))
    pos = [jnp.tile(jnp.arange(seq, dtype=jnp.int32), (B, 1))] \
        if positions else []

    def program(x, w):
        return op.forward([x] * inputs + pos, [w[n] for n in names],
                          training=True)[0]

    def plain(x, w):
        with jax.default_matmul_precision("highest"):
            return jnp.stack([reference(row, w) for row in x])

    close(program(x, w), plain(x, w))
    got = jax.grad(lambda x, w: jnp.sum(program(x, w) * probe),
                   argnums=(0, 1))(x, w)
    want = jax.grad(lambda x, w: jnp.sum(plain(x, w) * probe),
                    argnums=(0, 1))(x, w)
    close(got[0], want[0], grad_tol)
    for n in names:
        close(got[1][n], want[1][n], dict(leaf_tol).get(n, grad_tol))
    return op


@pytest.mark.parametrize("seq, plan", [
    (16, "chunked"), (40, "chunked"), (80, "chunked"),
    (40, "chunked_kernel"), (80, "chunked_kernel")])
def test_kda_op_matches_the_reference_forward_and_gradient(seq, plan,
                                                           monkeypatch):
    """40 is a ragged chunk of sub-chunks, 80 two chunks of 64.  Under
    both plans: the kernels' (interpreted here, at the toy head width)
    take q~, k~, v, g flat and normalise q~, k~ themselves."""
    if plan != "chunked":
        monkeypatch.setattr(kda_op, "pick_recurrence", lambda *a: plan)
    op = op_alone(
        lambda ff, x, _: ff.kimi_delta_attention(x, kda_params(), name="op"),
        lambda a, w: fam.kda(a, w, D, lambda v: v),
        fam.mixer_shapes(D, "kda"), seq=seq,
        # Read (this file's `close`, the largest of seq 16 / 40 / 80):
        # `chunked` every leaf and dx <= 6.8e-6 but A_log's gradient,
        # h numbers that are each a sum over b s d products and, with g
        # formed flat, summed over the positions first: 3.7e-6 / 5.7e-6
        # / 1.01e-5 (by head, before PR 45: 2.8e-6 / 6.0e-6 / 7.7e-6);
        # `chunked_kernel` (seq 40 / 80) every leaf and dx <= 8.1e-6,
        # A_log 5.4e-6 / 1.13e-5: held to the kernels' own tests' bound
        # (tests/test_gated_delta_rule.py).
        grad_tol=OP_TOL if plan == "chunked" else GROUP_TOL,
        leaf_tol={"A_log": GROUP_TOL})
    assert op.recurrence_plan(seq) == plan
    assert op.chunk_tokens(seq) == cdr.pick_chunk(seq)[0] > 0


@pytest.mark.parametrize("heads, dim", [(3, 8), (2, 128)])
def test_head_rms_is_the_norm_by_head_without_the_by_head_form(heads, dim):
    """The heads' sums of squares as a product with the membership
    matrix, the rsqrt spread back by its transpose: the by-head
    formula's value and gradient on `[b, s, h d]`."""
    o = jax.random.normal(jax.random.key(3), (2, 5, heads * dim)) * 3.0
    probe = jax.random.normal(jax.random.key(4), o.shape)

    def by_head(o):
        t = o.reshape(2, 5, heads, dim)
        t = t * jax.lax.rsqrt(jnp.mean(jnp.square(t), -1, keepdims=True)
                              + 1e-5)
        return t.reshape(o.shape)

    def flat(o):
        return kda_op.head_rms(o, heads, 1e-5)

    close(flat(o), by_head(o))
    close(jax.grad(lambda o: jnp.sum(flat(o) * probe))(o),
          jax.grad(lambda o: jnp.sum(by_head(o) * probe))(o))


@pytest.mark.parametrize("core", ["dense", "flash"])
def test_nope_mla_op_matches_the_reference_forward_and_gradient(core):
    """`flash`: the op's own rule (`flash_min_seq`) sends the core
    through `flash_mha`, whose twin runs on the CPU: keys of 12 padded
    to 128 against values of 8."""
    def prepare(op):
        op._flash_min_seq = 1 if core == "flash" else 1 << 20
        assert op.core_plan() == core

    op_alone(lambda ff, x, _: ff.mla_attention(x, None, mla_params(),
                                               name="op"),
             lambda a, w: fam.mla(a, w, D, lambda v: v),
             fam.mixer_shapes(D, "mla"), prepare=prepare)


def test_mla_with_positions_refuses_to_go_without_them_and_the_reverse():
    ff = FFModel(FFConfig(batch_size=B, num_devices=1))
    x = ff.create_tensor([B, S, D["e"]], name="x")
    pos = ff.create_tensor([B, S], dtype="int32", name="positions")
    with pytest.raises(ValueError, match="positions"):
        ff.mla_attention(x, None, mla_params(nope=False), name="a")
    with pytest.raises(ValueError, match="positions"):
        ff.mla_attention(x, pos, mla_params(), name="b")


def test_expanded_mla_with_bottleneck_and_rotation_takes_a_gradient():
    """ROADMAP R0(c): cell 4's op (query bottleneck, YaRN rotation) on
    its stateless path against families/kimi_k2's reference, forward
    and gradient; its weights are the seven it always had."""
    cfg = config("toy-kimi.json")
    d = fam_k2.dims(cfg)
    from flexflow_tpu.models.kimi_k2 import build_kimi_k2

    holder = FFModel(FFConfig(batch_size=1, num_devices=1))
    build_kimi_k2(holder, 1, 8, **fam_k2.published(cfg))
    params = next(op for op in holder.layers.topo_order()
                  if op.name == "attn_0").params
    assert params.q_lora_rank > 0 and not params.nope
    shapes = fam_k2.leaf_shapes(d, "attn")
    assert list(shapes) == ["wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
                            "wkv_b", "wo"]
    op_alone(lambda ff, x, pos: ff.mla_attention(x, pos, params, name="op"),
             lambda a, w: fam_k2.attention(a, w, d, lambda v: v),
             shapes, seq=12, positions=True, embed=d.e)


def test_gated_delta_net_stateless_takes_a_gradient_through_the_chunks():
    """ROADMAP R0(c): `GatedDeltaNet`'s stateless shape (one decay a
    head, broadcast over the channels) against families/qwen3_next's
    reference, forward and gradient."""
    cfg = config("toy-qwen3-next.json")
    d = fam_q3.dims(cfg)
    from flexflow_tpu.models.qwen3_next import build_qwen3_next

    holder = FFModel(FFConfig(batch_size=1, num_devices=1))
    build_qwen3_next(holder, 1, 8, **fam_q3.published(cfg))
    gdn = next(op for op in holder.layers.topo_order()
               if op.name.startswith("gdn_"))
    op = op_alone(
        lambda ff, x, _: ff.gated_delta_net(x, gdn.params, name="op"),
        lambda a, w: fam_q3.delta_net(a, w, d, lambda v: v),
        fam_q3.leaf_shapes(d, "gdn"), seq=40, embed=d.e)
    assert op.recurrence_plan(40) == "chunked"


def test_kda_flops_count_the_recurrence_and_the_products():
    ff = FFModel(FFConfig(batch_size=B, num_devices=1))
    op = ff.kimi_delta_attention(ff.create_tensor([B, S, D["e"]], name="x"),
                                 kda_params(), name="op").owner_op
    e, h, d, k = D["e"], D["kh"], D["kd"], D["taps"]
    c = h * d
    assert op.flops() == B * S * (
        2.0 * (4 * e * c + 2 * (e * d + d * c) + e * h)
        + 2.0 * 3 * c * k + 7.0 * h * d * d)
    # the family counts the same products a token and the same core
    assert fam.macs_per_token(CFG)["kda"] == D["kinds"].count("kda") * (
        4 * e * c + 2 * (e * d + d * c) + e * h + 3 * c * k)
    assert fam.kda_core_flops(CFG, B, S) == 3 * 7.0 * D["kinds"].count(
        "kda") * B * S * h * d * d


# -- 3. the whole model: logits and the first-step gradient ---------------------
@functools.lru_cache(maxsize=None)
def seeded(layout):
    """The seed's weights, made once a layout (the balancing rule runs
    the reference's layers forward: seconds even at the toy size)."""
    return fam.make_weights(CFG, SEED, layout)


def weights():
    return jax.tree.map(np.asarray, seeded("program"))


def batch(cfg=CFG, seed=5):
    return fam.make_batch(cfg, B, S, np.random.default_rng(seed))


def compiled(cfg=CFG):
    ff = fam.build_model(cfg, B, S, 1)
    fam.compile_model(ff, cfg, jax.devices()[:1])
    ff.set_weights(weights())
    return ff


def test_builder_reads_the_layer_pattern_from_linear_attn_config():
    assert layer_kinds(CFG["linear_attn_config"], 4) == [
        "kda", "kda", "mla", "kda"]
    published = config("kimi-linear-ep32-train.json")
    assert layer_kinds(published["published"]["linear_attn_config"], 27) == (
        ["kda", "kda", "kda", "mla"] * 6 + ["kda", "kda", "mla"])
    assert layer_kinds(published["linear_attn_config"], 5) == [
        "kda", "kda", "kda", "mla", "kda"]
    with pytest.raises(ConfigError, match="each of layers"):
        layer_kinds({"kda_layers": [1, 2], "full_attn_layers": [2]}, 3)
    ff = FFModel(FFConfig(batch_size=1, num_devices=1))
    with pytest.raises(ConfigError, match="topk_group"):
        build_kimi_linear(ff, 1, 8, **{**{k: CFG[k] for k in (
            "hidden_size", "num_hidden_layers", "linear_attn_config")},
            "num_expert_group": 8, "topk_group": 4})


def test_logits_equal_the_reference():
    ff = compiled()
    inputs, _ = batch()
    with jax.default_matmul_precision("highest"):
        want = np.stack([fam.logits_fn(weights(), jnp.asarray(row), CFG)
                         for row in inputs["input"]])
    close(ff.forward(inputs), want, LOGIT_TOL)


@pytest.mark.parametrize("remat", [False, True])
def test_first_step_gradient_equals_the_reference_by_group(remat):
    cfg = dict(CFG, assumed=dict(CFG["assumed"], remat=remat))
    before = len(trace.spans())
    ff = compiled(cfg)
    inputs, labels = batch()
    loss = float(ff.train_step(inputs, labels)["loss"])
    want = fam.reference_grads(seeded("reference"), inputs["input"], labels)
    scale = 1.0 / (1.0 - CFG["optimizer"]["beta1"])
    got = fam.to_reference_layout(jax.tree.map(
        lambda m: np.asarray(m, np.float32) * scale, ff._opt_state["m"]))
    stats = check.group_rel_l2(got, want, fam.GROUPS)
    assert set(stats) == set(fam.GROUPS) == {
        k[len("grad."):] for k in CFG["tolerance"]}
    assert max(stats.values()) <= GROUP_TOL, stats
    assert not any(np.any(v["router_bias"])
                   for v in got["choosing_bias"].values())
    with jax.default_matmul_precision("highest"):
        ref_loss = np.mean([float(fam.sequence_loss(
            weights(), jnp.asarray(i), jnp.asarray(lab), CFG, "float32"))
            for i, lab in zip(inputs["input"], labels)])
    assert abs(loss - ref_loss) <= 1e-5 * ref_loss
    # the step the program built says which chunk its cores took
    built = [r for r in trace.spans()[before:] if r.name == "build_step_fns"]
    assert built and built[-1].args["kda_chunk_tokens"] == 16
    assert built[-1].args["kda_kernel_ops"] == 0  # the CPU's plan
    assert ff.executor.remat_segments == (8 if remat else 0) or remat


def test_build_step_fns_counts_the_chunk_kernels_and_their_chunk(
        monkeypatch):
    """The toy model at heads of 128 and rows of one chunk: with a
    TPU's answer from `pick_recurrence` the `build_step_fns` span
    counts the three KDA layers' kernels and reports their chunk; the
    CPU's plan reports the same chunk and no kernel."""
    cfg = dict(CFG, linear_attn_config=dict(
        CFG["linear_attn_config"], head_dim=128, num_heads=2))

    def built():
        before = len(trace.spans())
        ff = fam.build_model(cfg, 1, 64, 1)
        fam.compile_model(ff, cfg, jax.devices()[:1])
        return [r.args for r in trace.spans()[before:]
                if r.name == "build_step_fns"][-1]

    args = built()
    assert (args["kda_chunk_tokens"], args["kda_kernel_ops"]) == (64, 0)
    monkeypatch.setattr(  # the ops ask `pick_recurrence` as a TPU would
        kda_op, "pick_recurrence",
        lambda backend, *a: gdr.pick_recurrence("tpu", *a))
    k_args = built()
    assert (k_args["kda_chunk_tokens"], k_args["kda_kernel_ops"]) == (64, 3)
    assert k_args["remat_segments"] == args["remat_segments"] > 0


def test_a_twin_of_the_trainer_graph_is_refused_by_name():
    from flexflow_tpu.decoding import decoder_recipe

    with pytest.raises(ConfigError, match="kimi_linear is built for "
                                          "training only"):
        decoder_recipe(fam.build_model(CFG, 1, 8, 1))


def test_parameter_count_of_the_published_cut_is_the_deployments():
    cfg = config("kimi-linear-ep32-train.json")
    assert fam.parameter_count(cfg) == cfg["deployment"]["parameters"] \
        == 602_434_432
    shapes = fam.op_shapes(cfg)
    count = {op: sum(int(np.prod(s)) for s in leaves.values())
             for op, leaves in shapes.items()}
    assert count["kda_0"] == 39_514_272 and count["mla_3"] == 29_114_880
    assert count["mlp_0"] == 63_700_992
    assert shapes["kda_0"]["f_b_proj"] == (128, 4096)  # a decay a channel
    assert shapes["mla_3"]["wq"] == (2304, 32, 192)    # no bottleneck


# -- 4. the share test -----------------------------------------------------------
def test_every_share_of_an_expert_layer_adds_up_to_the_uncut_layer():
    """The routed parts that all `total / held` shares give through the
    PROGRAM's op, with the shared expert counted once, are the uncut
    reference's whole layer (every expert of the router's width over
    every row, plus the shared expert)."""
    total, held, e, fe = D["total"], 2, D["e"], D["fe"]
    keys = jax.random.split(jax.random.key(7), 9)
    whole = {"router": (e, total), "router_bias": (total,),
             "w_gate": (total, e, fe), "w_up": (total, e, fe),
             "w_down": (total, fe, e), "shared_gate": (e, D["fs"]),
             "shared_up": (e, D["fs"]), "shared_down": (D["fs"], e)}
    w = {n: 0.3 * jax.random.normal(k, s)
         for k, (n, s) in zip(keys, whole.items())}
    x = jax.random.normal(keys[-1], (B, S, e))
    q = lambda v: v  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([
            fam.routed_part(row, w, D, q, first=0, held=total)
            + fam.shared_part(row, w, q) for row in x])
        shared = jnp.stack([fam.shared_part(row, w, q) for row in x])
    summed = jnp.zeros_like(want)
    for first in range(0, total, held):
        ff = FFModel(FFConfig(batch_size=B, num_devices=1))
        op = ff.routed_experts(
            ff.create_tensor([B, S, e], name="x"), RoutedExpertsParams(
                experts_total=total, experts_held=held, first_held=first,
                top_k=D["k"], expert_hidden=fe, shared_hidden=D["fs"],
                routed_scaling_factor=D["scale"]), name="op").owner_op
        mine = {n: (v[first:first + held] if n in ("w_gate", "w_up", "w_down")
                    else v) for n, v in w.items()}
        names = [spec.name for spec in op.weight_specs]
        state = [jnp.zeros(s.shape.logical_shape, jnp.int32)
                 for s in op.weight_specs[len(whole):]]
        assert names[:len(whole)] == list(whole)
        out = op.forward([x], [mine[n] for n in names[:len(whole)]] + state,
                         training=True)[0]
        summed = summed + (out - shared)
    close(summed + shared, want)


# -- 5. the flash kernels at keys of 192, values of 128 --------------------------
def test_flash_kernels_at_192_wide_keys_and_128_wide_values_interpreted():
    """What `flash_mha` hands the long-row kernels for the published MLA
    widths: q and k padded from 192 to 256 lanes, v at 128; forward and
    both backward kernels in interpret mode against `_ref_attention` on
    the UNPADDED operands."""
    bh, s, dqk, dv = 2, 256, 192, 128
    keys = jax.random.split(jax.random.key(3), 4)
    q, k = (jax.random.normal(kk, (bh, s, dqk)) for kk in keys[:2])
    v = jax.random.normal(keys[2], (bh, s, dv))
    dout = jax.random.normal(keys[3], (bh, s, dv))
    scale = dqk ** -0.5
    assert fa.lane_width(dqk) == 256 and fa.lane_width(64) == 64
    pad = ((0, 0), (0, 0), (0, 256 - dqk))
    qp, kp = jnp.pad(q, pad), jnp.pad(k, pad)
    assert fa._supported(qp, kp, v=v) and not fa._supported(q, k, v=v)
    want, vjp = jax.vjp(lambda q, k, v: fa._ref_attention(
        q, k, v, scale, True), q, k, v)
    out, lse = fa._flash_fwd_pallas(qp, kp, v, scale, True, 128, 128,
                                    interpret=True)
    assert out.shape == (bh, s, dv)
    close(out, want, 2e-5)
    dq, dk, dvv = fa._flash_bwd_pallas(qp, kp, v, out, lse, dout, scale,
                                       True, 128, 128, interpret=True)
    assert dq.shape == qp.shape and dvv.shape == v.shape
    for got, ref in zip((dq[..., :dqk], dk[..., :dqk], dvv), vjp(dout)):
        close(got, ref, 2e-5)
    # the pad's channels take no gradient
    assert not np.any(np.asarray(dq[..., dqk:])) \
        and not np.any(np.asarray(dk[..., dqk:]))


def test_equal_widths_lower_the_flash_kernels_as_before():
    """Cells 1, 3 and 6: keys and values of one width ask for no VMEM
    beyond the default and take `flash_mha`'s old branches."""
    q = jnp.zeros((4, 4096, 64), jnp.bfloat16)
    assert fa._resident_vmem(q, q) == {}
    wide = jnp.zeros((32, 8192, 256), jnp.bfloat16)
    assert "compiler_params" in fa._resident_vmem(wide, wide[..., :128])
