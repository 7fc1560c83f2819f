"""kimi_linear's ops alone against the plain float32 reference
(benchmarks/families/kimi_linear.py, whose recurrence runs a position
at a time), at a toy size on the CPU:

1. each new op, forward and gradient, and the gradient tests ROADMAP
   R0(c) said were missing (the expanded MLA path with its bottleneck
   and rotation, `GatedDeltaNet`'s stateless shape);
2. the share test of the model-configs guide;
3. the flash kernels at keys of 192 (padded to 256) with values of 128,
   in interpret mode.

The chunked rule against the scan: tests/test_kimi_linear_rule.py; the
whole model: tests/test_kimi_linear.py.  Tolerances as
tests/test_lfm2_moe_ops.py: the same float32 arithmetic in another
order.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _family import OP_TOL, close, config, op_alone, reference_side

from benchmarks.families import kimi_k2 as fam_k2
from benchmarks.families import kimi_linear as fam
from benchmarks.families import qwen3_next as fam_q3
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.ops import chunked_delta_rule as cdr
from flexflow_tpu.ops import kimi_delta_attention as kda_op
from flexflow_tpu.ops.kimi_delta_attention import KimiDeltaAttentionParams
from flexflow_tpu.ops.mla import MLAParams
from flexflow_tpu.ops.pallas import flash_attention as fa
from flexflow_tpu.ops.routed_experts import RoutedExpertsParams

CFG = config("toy-kimi-linear.json")
D = fam.dims(CFG)
B, S = 2, 16
GROUP_TOL = 2e-5


# -- 1. each op alone, forward and gradient ------------------------------------
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


@functools.lru_cache(maxsize=None)
def kda_case(seq):
    """The reference's side at `seq`, once for both plans."""
    return reference_side(lambda a, w: fam.kda(a, w, D, lambda v: v),
                          fam.mixer_shapes(D, "kda"), D["e"], seq)


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
        kda_case(seq), jit=plan == "chunked",
        # Read (this file's `close`, the largest of seq 16 / 40 / 80):
        # `chunked` every leaf and dx <= 6.8e-6 but A_log's gradient,
        # h numbers that are each a sum over b s d products and, with g
        # formed flat, summed over the positions first: 3.7e-6 / 5.7e-6
        # / 1.01e-5 (by head, before PR 45: 2.8e-6 / 6.0e-6 / 7.7e-6);
        # `chunked_kernel` (seq 40 / 80) every leaf and dx <= 8.1e-6,
        # A_log 5.4e-6 / 1.13e-5: held to the kernels' own tests' bound
        # (tests/test_chunked_delta_kernels.py).
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

    close(jax.jit(flat)(o), jax.jit(by_head)(o))
    close(jax.jit(jax.grad(lambda o: jnp.sum(flat(o) * probe)))(o),
          jax.jit(jax.grad(lambda o: jnp.sum(by_head(o) * probe)))(o))


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
             reference_side(lambda a, w: fam.mla(a, w, D, lambda v: v),
                            fam.mixer_shapes(D, "mla"), D["e"]),
             prepare=prepare)


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
             reference_side(
                 lambda a, w: fam_k2.attention(a, w, d, lambda v: v),
                 shapes, d.e, seq=12), positions=True)


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
        reference_side(lambda a, w: fam_q3.delta_net(a, w, d, lambda v: v),
                       fam_q3.leaf_shapes(d, "gdn"), d.e, seq=40))
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

    @jax.jit
    def reference(x, w):
        with jax.default_matmul_precision("highest"):
            shared = jnp.stack([fam.shared_part(row, w, q) for row in x])
            return shared + jnp.stack([fam.routed_part(
                row, w, D, q, first=0, held=total) for row in x]), shared

    want, shared = reference(x, w)
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
