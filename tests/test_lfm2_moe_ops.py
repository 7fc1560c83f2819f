"""lfm2_moe's ops alone (gated short convolutions, grouped-query
attention, RMSNorm, the gated MLP, sigmoid-routed experts under a dense
or a grouped product) against the plain float32 reference
(benchmarks/families/lfm2_moe.py), forward and gradient, at a toy size
on the CPU; the grouped product against the dense one at any load, its
hand-written backward rule, the share test, and which product a shape
takes.  The whole model: tests/test_lfm2_moe.py.

Tolerances.  The program and the reference compute the same float32
arithmetic in another order, so they differ by rounding only: 1e-5 of
the compared tensor's largest magnitude for one op's output or
gradient (`_family.OP_TOL`).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _family import close, config, equations, op_alone, reference_side

from benchmarks.families import lfm2_moe as fam
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.ops import routed_experts as rx
from flexflow_tpu.ops.routed_experts import (RoutedExpertsParams,
                                             pick_expert_product)

CFG = config("toy-lfm2.json")
D = fam.dims(CFG)
SEED = 13
B, S = 2, 16


# -- 1. each op alone, forward and gradient ------------------------------------
def experts_params(cfg=CFG, **kw):
    d = fam.dims(cfg)
    return RoutedExpertsParams(**{**dict(
        experts_total=d["total"], experts_held=d["held"],
        first_held=d["first"], top_k=d["k"], expert_hidden=d["fe"],
        routed_scaling_factor=d["scale"], norm_eps=fam.ROUTER_EPS), **kw})


def op_cases():
    """{name: (build(ff, x) -> tensor, program weight names, reference
    (a [s, e], {leaf}) -> [s, e], a leaf tree's shapes)}."""
    e, hd = D["e"], D["d"]
    four_to_one = dict(D, heads=4, kv=1)
    return {
        "short_conv": (
            lambda ff, x: ff.short_conv(x, D["taps"], name="op"),
            lambda a, w: fam.short_conv(a, w, D["taps"], lambda v: v),
            {"in_proj": (e, 3 * e), "conv": (e, D["taps"]),
             "out_proj": (e, e)}),
        "attention_4_to_1": (
            lambda ff, x: ff.multihead_attention(
                x, x, x, e, 4, name="op", kdim=4 * hd, vdim=4 * hd,
                causal=True, num_kv_heads=1, qk_norm=True,
                norm_eps=D["eps"], rotary_dim=hd, rope_theta=D["theta"]),
            lambda a, w: fam.attention(a, w, four_to_one, lambda v: v),
            {"wq": (e, 4, hd), "wk": (e, 1, hd), "wv": (e, 1, hd),
             "wo": (4, hd, e), "q_norm": (hd,), "k_norm": (hd,)}),
        "rms_norm": (
            lambda ff, x: ff.rms_norm(x, D["eps"], name="op"),
            lambda a, w: fam.rms(a, w["gamma"], D["eps"]),
            {"gamma": (e,)}),
        "gated_mlp": (
            lambda ff, x: ff.gated_mlp(x, D["f"], name="op"),
            lambda a, w: fam.gated(a, w["w_gate"], w["w_up"], w["w_down"],
                                   lambda v: v),
            {"w_gate": (e, D["f"]), "w_up": (e, D["f"]),
             "w_down": (D["f"], e)}),
        "routed_experts": (
            lambda ff, x: ff.routed_experts(x, experts_params(), name="op"),
            lambda a, w: fam.routed(a, w, D, lambda v: v),
            {"router": (e, D["total"]), "router_bias": (D["total"],),
             "w_gate": (D["held"], e, D["fe"]),
             "w_up": (D["held"], e, D["fe"]),
             "w_down": (D["held"], D["fe"], e)}),
    }


@pytest.mark.parametrize("name", [
    "short_conv", "attention_4_to_1", "rms_norm", "gated_mlp",
    "routed_experts", "routed_experts_grouped"])
def test_op_alone_matches_the_reference_forward_and_gradient(
        name, monkeypatch):
    if name.endswith("_grouped"):
        monkeypatch.setattr(rx, "GROUPED_MIN_ROWS_PER_EXPERT", 1)
    build, reference, shapes = op_cases()[name.replace("_grouped", "")]
    op = op_alone(lambda ff, x, _: build(ff, x),
                  reference_side(reference, shapes, D["e"]),
                  inputs=3 if name.startswith("attention") else 1,
                  chooses=("router_bias",))
    if name.startswith("routed_experts"):
        assert op.product_plan() == ("grouped" if name.endswith("_grouped")
                                     else "dense")


# -- 3. grouped == dense, at any load -------------------------------------------
def layer_by(product_plan, w, x, monkeypatch):
    monkeypatch.setattr(rx, "GROUPED_MIN_ROWS_PER_EXPERT",
                        1 if product_plan == "grouped" else 10 ** 9)
    ff = FFModel(FFConfig(batch_size=B, num_devices=1))
    op = ff.routed_experts(ff.create_tensor([B, S, D["e"]], name="x"),
                           experts_params(), name="op").owner_op
    assert op.product_plan() == product_plan
    names = [s.name for s in op.weight_specs[:5]]
    state = [jnp.zeros(s.shape.logical_shape, jnp.int32)
             for s in op.weight_specs[5:]]

    def run(x, w):
        out = op.forward([x], [w[n] for n in names] + state, training=True)
        return out[0], out[1:]

    probe = jax.random.normal(jax.random.key(3), x.shape)
    (_, (out, state)), grads = jax.value_and_grad(
        lambda x, w: (lambda o: (jnp.sum(o[0] * probe), o))(run(x, w)),
        argnums=(0, 1), has_aux=True)(x, w)
    return out, state, grads


@pytest.mark.parametrize("load", ["uniform", "all_on_one_held_expert",
                                  "none_on_a_held_expert"])
def test_grouped_product_equals_the_dense_one_at_any_load(load, monkeypatch):
    keys = jax.random.split(jax.random.key(SEED), 7)
    e, n, fe, total = D["e"], D["held"], D["fe"], D["total"]
    w = {"router": 0.3 * jax.random.normal(keys[0], (e, total)),
         "router_bias": jnp.zeros((total,)),
         "w_gate": 0.3 * jax.random.normal(keys[1], (n, e, fe)),
         "w_up": 0.3 * jax.random.normal(keys[2], (n, e, fe)),
         "w_down": 0.3 * jax.random.normal(keys[3], (n, fe, e))}
    held = range(D["first"], D["first"] + n)
    if load == "all_on_one_held_expert":
        w["router_bias"] = w["router_bias"].at[D["first"] + 1].set(100.0)
    elif load == "none_on_a_held_expert":
        w["router_bias"] = w["router_bias"].at[jnp.asarray(held)].set(-100.0)
    x = jax.random.normal(keys[4], (B, S, e))
    d_out, d_state, d_grads = layer_by("dense", w, x, monkeypatch)
    g_out, g_state, g_grads = layer_by("grouped", w, x, monkeypatch)
    close(g_out, d_out)
    close(g_grads[0], d_grads[0])
    for leaf in w:
        close(g_grads[1][leaf], d_grads[1][leaf])
    pairs, dropped, max_rows, hit = (int(v) for v in g_state[0])
    assert np.array_equal(g_state[0], d_state[0]) and dropped == 0
    rows_computed = int(g_state[1][0])
    if load == "none_on_a_held_expert":
        assert pairs == hit == rows_computed == 0
        assert not np.any(np.asarray(g_out))
        assert not any(np.any(np.asarray(v)) for v in
                       jax.tree.leaves(g_grads))
    elif load == "all_on_one_held_expert":
        assert max_rows == B * S and pairs >= B * S
    if pairs:  # only what routing asked, up to a row tile an expert
        assert pairs <= rows_computed <= pairs + hit * (rx.GROUPED_ROW_TILE
                                                        - 1)


def test_the_grouped_buffers_hold_every_pair_when_the_usual_ones_overflow(
        monkeypatch):
    """`grouped_experts` keeps `GROUPED_SLACK` x the expected pairs and,
    on a step with more, every pair: the same result either way."""
    keys = jax.random.split(jax.random.key(1), 6)
    t, k, e, n, f = 24, 2, 8, 3, 8
    h = jax.random.normal(keys[0], (t, e))
    landed_on = jax.random.randint(keys[1], (t, k), 0, n + 1)
    w = jax.random.uniform(keys[2], (t, k))
    ws = [0.3 * jax.random.normal(kk, s) for kk, s in zip(
        keys[3:], [(n, e, f), (n, e, f), (n, f, e)])]
    count = int(jnp.sum(landed_on < n))
    outs = [rx.grouped_experts(h, landed_on, w, *ws, expected)[0]
            for expected in (t * k, count / rx.GROUPED_SLACK + 8, 1.0)]
    close(outs[1], outs[0])
    close(outs[2], outs[0])


def grouped_case(load, size):
    """(h, landed_on, w, [w_gate, w_up, w_down], expected_pairs) of one
    call of `grouped_experts`, t x k = 48 pairs on n = 3 held experts
    (`landed_on` n: an expert that lives elsewhere), and whether its
    held pairs overflow the usual buffers.  `size`: "usual" buffers
    that hold the step's pairs, buffers an "overflow" step runs over,
    or "one_size": the usual buffers hold every pair at any load."""
    keys = jax.random.split(jax.random.key(1), 6)
    t, k, e, n, f = 24, 2, 8, 3, 12
    h = jax.random.normal(keys[0], (t, e))
    landed_on = {
        "light": jnp.where(jax.random.uniform(keys[1], (t, k)) < 0.25,
                           jax.random.randint(keys[1], (t, k), 0, n), n),
        "even": jax.random.randint(keys[1], (t, k), 0, n + 1),
        "every_pair_on_one_expert": jnp.full((t, k), 1),
    }[load]
    w = jax.random.uniform(keys[2], (t, k))
    ws = [0.3 * jax.random.normal(kk, s) for kk, s in zip(
        keys[3:], [(n, e, f), (n, e, f), (n, f, e)])]
    count = int(jnp.sum(landed_on < n))
    assert count > rx.GROUPED_ROW_TILE
    expected = {"usual": count / rx.GROUPED_SLACK + 1, "overflow": 1.0,
                "one_size": t * k}[size]
    return (h, landed_on, w, ws, expected), size == "overflow"


def m_usual_of(t_k, expected):
    return min(t_k, -(-int(rx.GROUPED_SLACK * expected)
                      // rx.GROUPED_ROW_TILE) * rx.GROUPED_ROW_TILE)



def unwritten_tails(monkeypatch):
    """The CPU's grouped product gives zeros past the last group; the
    chip's leaves those rows unwritten.  Stand in for it: NaN there, out
    of `grouped_matmul` and (through it) `grouped_matmul_into_lhs`."""
    product = rx.grouped_matmul

    def chips(lhs, rhs, sizes, *rows_a_group):
        out = product(lhs, rhs, sizes)
        written = jnp.arange(out.shape[0]) < jnp.sum(sizes)
        return jnp.where(written[:, None], out, jnp.nan)

    monkeypatch.setattr(rx, "grouped_matmul", chips)


@pytest.mark.parametrize("tails", ["zeros", "unwritten"])
@pytest.mark.parametrize("size", ["usual", "overflow", "one_size"])
@pytest.mark.parametrize("load", ["light", "even",
                                  "every_pair_on_one_expert"])
def test_grouped_gradients_equal_the_dense_ones_on_either_size(
        load, size, tails, monkeypatch):
    """The hand-written backward rule against autodiff of the dense
    product: into the rows, the routing weights and the three expert
    weights, on the usual buffers and on the every-pair ones (whose
    backward runs their forward again); and the same with NaN in every
    row the products do not write on the chip, which nothing may
    read."""
    if tails == "unwritten":
        unwritten_tails(monkeypatch)
    (h, landed_on, w, ws, expected), overflows = grouped_case(load, size)
    n = ws[0].shape[0]
    probe = jax.random.normal(jax.random.key(3), h.shape)

    landed = jax.nn.one_hot(landed_on, n, dtype=jnp.float32)

    def dense(h, w, *ws):
        out = rx.dense_experts(h, jnp.einsum("tkx,tk->tx", landed, w), *ws)
        return jnp.sum(probe * out), out

    def grouped(h, w, *ws):
        out, counts = rx.grouped_experts(h, landed_on, w, *ws, expected)
        return jnp.sum(probe * out), (out, counts)

    want, dense_out = jax.grad(dense, argnums=range(5), has_aux=True)(
        h, w, *ws)
    got, (out, counts) = jax.grad(grouped, argnums=range(5), has_aux=True)(
        h, w, *ws)
    close(out, dense_out)
    for g, d in zip(got, want):
        close(g, d)
    assert int(counts[1]) == overflows
    # a pair on an expert that lives elsewhere moves nothing
    assert not np.any(np.asarray(got[1])[np.asarray(landed_on) == n])


def test_the_backward_rule_holds_slot_sized_buffers_only():
    """What `jax.vjp(grouped_experts)` keeps for the backward pass: the
    arguments, the two permutations, the sizes and the usual buffers'
    three products; nothing with a row a pair (t x k, the every-pair
    size's) of an activation's width."""
    (h, landed_on, w, ws, expected), _ = grouped_case("even", "usual")
    (t, k), e, f = landed_on.shape, h.shape[1], ws[0].shape[2]
    m = m_usual_of(t * k, expected)
    assert m < t * k and len({t, m, t * k}) == 3
    _, pull = jax.vjp(lambda h, w, *ws: rx.grouped_experts(
        h, landed_on, w, *ws, expected)[0], h, w, *ws)
    kept = [x.shape for x in jax.tree.leaves(pull) if hasattr(x, "shape")]
    assert sorted(s for s in kept if s[:1] == (m,)) == sorted(
        [(m, f), (m, f), (m, e)])
    for shape in kept:
        assert not (shape[0] == t * k and shape[-1] in (e, f)
                    and len(shape) > 1), kept
        assert shape[:2] != (t, k) or len(shape) == 2, kept


def test_no_mask_pass_over_a_slot_buffer_in_the_gradient():
    """The lowered value and gradient select over no whole [m, e] or
    [m, f] buffer, at either size: the routing weight of a slot past
    the held runs is zero, no grouped product reads such a slot, and
    the one mask (the held pairs) is [t, e], inside the two sums over a
    token's slots."""
    (h, landed_on, w, ws, expected), _ = grouped_case("even", "usual")
    (t, k), e, f = landed_on.shape, h.shape[1], ws[0].shape[2]
    sizes = {m_usual_of(t * k, expected), t * k}
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda h, w, *ws: jnp.sum(rx.grouped_experts(
            h, landed_on, w, *ws, expected)[0]), argnums=range(5)))(
                h, w, *ws).jaxpr
    selects = [v.aval.shape for eqn in equations(jaxpr)
               if eqn.primitive.name == "select_n" for v in eqn.outvars]
    assert selects  # the walk does reach them
    assert not [s for s in selects
                if len(s) == 2 and s[0] in sizes and s[1] in (e, f)], selects
    products = [eqn for eqn in equations(jaxpr)
                if eqn.primitive.name == "ragged_dot_general"]
    # forward 3 + 3, backward 6 + (3 again + 6): the `cond`s' branches
    assert len(products) == 21


@pytest.mark.parametrize("form", ["grouped_matmul",
                                  "grouped_matmul_into_lhs",
                                  "grouped_matmul_into_rhs"])
def test_the_grouped_products_read_no_row_past_the_last_group(form):
    """What lets the layer run without a mask pass of its own: no form
    of the product reads a row past the last group (NaN there reaches
    no row of a group and no slice of a weight's gradient).  The rows
    the first two forms RETURN there are zeros on the CPU and unwritten
    on the chip (`scripts/expert_product_probe.py` says which): the
    layer reads neither."""
    keys = jax.random.split(jax.random.key(2), 3)
    m, e, f, n, count = 40, 8, 12, 3, 21
    live = (jnp.arange(m) < count)[:, None]
    clean_lhs = jnp.where(live, jax.random.normal(keys[0], (m, e)), 0)
    clean_ct = jnp.where(live, jax.random.normal(keys[1], (m, f)), 0)
    lhs, ct = (jnp.where(live, x, jnp.nan) for x in (clean_lhs, clean_ct))
    rhs = jax.random.normal(keys[2], (n, e, f))
    sizes = jnp.array([9, 0, 12], jnp.int32)
    if form == "grouped_matmul_into_rhs":
        got = rx.grouped_matmul_into_rhs(lhs, ct, sizes)
        assert got.shape == (n, e, f) and not np.any(np.asarray(got[1]))
        # it IS the gradient autodiff takes of the forward product
        want = jax.grad(lambda r: jnp.sum(
            rx.grouped_matmul(clean_lhs, r, sizes) * clean_ct))(rhs)
        close(got, want)
        return
    if form == "grouped_matmul":
        got = rx.grouped_matmul(lhs, rhs, sizes)
        want = jnp.concatenate([clean_lhs[:9] @ rhs[0],
                                clean_lhs[9:21] @ rhs[2]])
    else:
        got = rx.grouped_matmul_into_lhs(ct, rhs, sizes)
        want = jnp.concatenate([clean_ct[:9] @ rhs[0].T,
                                clean_ct[9:21] @ rhs[2].T])
    close(got[:count], want)
    assert not np.any(np.asarray(got[count:]))  # the CPU's lowering


# -- 4. the share test -----------------------------------------------------------
def test_the_four_shares_of_8_of_32_experts_add_up_to_the_uncut_layer():
    """At the published counts (32 experts, 8 held, top-4) and toy
    widths: the routed parts that the four shares give through the
    PROGRAM's op add up to the reference given every expert."""
    cfg = dict(CFG, num_experts=8, n_routed_experts_total=32,
               num_experts_per_tok=4, first_held_expert=0)
    d = fam.dims(cfg)
    keys = jax.random.split(jax.random.key(SEED), 6)
    e, fe = d["e"], d["fe"]
    whole = {"router": 0.3 * jax.random.normal(keys[0], (e, 32)),
             "router_bias": 0.02 * jax.random.normal(keys[1], (32,)),
             "w_gate": 0.3 * jax.random.normal(keys[2], (32, e, fe)),
             "w_up": 0.3 * jax.random.normal(keys[3], (32, e, fe)),
             "w_down": 0.3 * jax.random.normal(keys[4], (32, fe, e))}
    x = jax.random.normal(keys[5], (B, S, e))
    uncut = dict(d, held=32, first=0)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([fam.routed(row, whole, uncut, lambda v: v)
                          for row in x])
    total = 0.0
    for first in range(0, 32, 8):
        ff = FFModel(FFConfig(batch_size=B, num_devices=1))
        op = ff.routed_experts(
            ff.create_tensor([B, S, e], name="x"),
            experts_params(cfg, first_held=first), name="op").owner_op
        share = [whole["router"], whole["router_bias"]] + [
            whole[n][first:first + 8] for n in ("w_gate", "w_up", "w_down")]
        total = total + op.forward(
            [x], share + [jnp.zeros((4,), jnp.int32)])[0]
    close(total, want)


# -- 5. which product, by shape -----------------------------------------------
@pytest.mark.parametrize("rows,held,total,top_k,want", [
    (8192, 8, 32, 4, "grouped"),    # this family's training step
    (32768, 8, 32, 4, "grouped"),   # the same at the deployment's rows
    (32, 12, 384, 8, "dense"),      # cell 4's decode step
    (256, 12, 384, 8, "dense"),     # cell 4's prefill pass
    (64, 128, 512, 10, "dense"),    # cell 5's decode step
    (512, 128, 512, 10, "dense"),   # cell 5's prefill pass
    (32, 4, 8, 2, "dense"),         # the toy configuration's step
    (8192, 1, 32, 4, "dense"),      # one held expert: nothing to group
])
def test_pick_expert_product_by_shape(rows, held, total, top_k, want):
    for backend in ("tpu", "cpu"):
        assert pick_expert_product(rows, held, total, top_k, backend) == want


def test_flops_count_the_product_the_layer_takes(monkeypatch):
    def flops():
        ff = FFModel(FFConfig(batch_size=B, num_devices=1))
        return ff.routed_experts(
            ff.create_tensor([B, S, D["e"]], name="x"), experts_params(),
            name="op").owner_op.flops()

    rows, e = B * S, D["e"]
    router = 2.0 * rows * e * D["total"]
    one = 6.0 * rows * e * D["fe"]  # an expert over every row
    assert flops() == router + D["held"] * one
    monkeypatch.setattr(rx, "GROUPED_MIN_ROWS_PER_EXPERT", 1)
    assert flops() == router + D["k"] * D["held"] / D["total"] * one


def test_the_normaliser_epsilon_comes_from_the_params():
    h = jnp.ones((3, 4))
    router = jnp.zeros((4, 8))  # every score 0.5
    for eps in (1e-20, 0.5):
        p = RoutedExpertsParams(8, 2, 0, 2, 4, norm_eps=eps)
        _, w = rx.route(h, router, jnp.zeros((8,)), p)
        close(w, np.full((3, 2), 0.5 / (1.0 + eps)))
    assert RoutedExpertsParams(8, 2, 0, 2, 4).norm_eps == 1e-20
