"""The gated delta rule's serving kernel
(ops/pallas/gated_delta_rule.py) under the interpreter against the plain
`delta_rule_scan`, with the live-row list it walks, and the function
that picks between it, the training kernels, the jax.numpy rule and the
scan, and the ops that take the training kernels where it says so.  The
training kernels themselves (a chunk of positions at a time):
tests/test_chunked_delta_kernels.py.

Tolerance: the same float32 operations in the same order, the two
reductions over dk summed in another order: 1e-5 of the compared
tensor's largest magnitude, the op's own (tests/test_qwen3_next.py).
The kernel at the serving cell's widths is compiled for a v5e in
tests/test_tpu_bringup.py; served end to end at a toy size in
tests/test_qwen3_next.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _family import close, equations

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.ops import gated_delta_net as gdn_op
from flexflow_tpu.ops import kimi_delta_attention as kda_op
from flexflow_tpu.ops.gated_delta_net import delta_rule_scan as scanned
from flexflow_tpu.ops.kimi_delta_attention import KimiDeltaAttentionParams
from flexflow_tpu.ops.pallas import chunked_delta_rule as cdk
from flexflow_tpu.ops.pallas import gated_delta_rule as gdr

HEADS, DK, DV = 4, 128, 128


def inputs(counts, s, seed=0, h=HEADS, dk=DK, dv=DV):
    """A step as `GatedDeltaNet.forward` hands it over: non-zero state,
    unit keys, scaled unit queries, `g = beta = 0` past a row's count."""
    counts = np.asarray(counts)
    b, r = len(counts), np.random.default_rng(seed)
    k = r.normal(size=(b, s, h, dk))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q = r.normal(size=(b, s, h, dk))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * dk ** 0.5
    real = (np.arange(s)[None, :] < counts[:, None])[..., None]
    g = np.where(real, -r.uniform(0, 2, (b, s, h)), 0.0)
    beta = np.where(real, r.uniform(0, 1, (b, s, h)), 0.0)
    arrs = (r.normal(size=(b, h, dk, dv)), q, k,
            r.normal(size=(b, s, h, dv)), g, beta)
    return ([jnp.asarray(a, jnp.float32) for a in arrs]
            + [jnp.asarray(counts, jnp.int32)])


#: step length -> counts of one batch: none, some and all of the step
BATCHES = {1: [1, 0, 1, 0, 0, 1], 8: [8, 0, 3, 0, 8, 1]}


@pytest.mark.parametrize("heads_block", [None, 1, 2])
@pytest.mark.parametrize("s", sorted(BATCHES))
def test_kernel_equals_the_scanned_step(s, heads_block):
    counts = np.array(BATCHES[s])
    xs = inputs(counts, s, seed=s)
    S_want, o_want = jax.jit(scanned)(*xs[:6])
    S, o = gdr.gated_delta_rule(*xs, interpret=True,
                                heads_block=heads_block)
    live = counts > 0
    close(np.asarray(S)[live], np.asarray(S_want)[live])
    # the real positions' outputs (a pad's is computed and thrown away
    # on both paths)
    real = np.arange(s)[None, :] < counts[:, None]
    close(np.asarray(o)[real], np.asarray(o_want)[real])


@pytest.mark.parametrize("s", sorted(BATCHES))
def test_rows_that_do_not_advance_are_their_input_to_the_byte(s):
    counts = np.array(BATCHES[s])
    xs = inputs(counts, s, seed=10 + s)
    before = np.asarray(xs[0])
    S, o = map(np.asarray, gdr.gated_delta_rule(*xs, interpret=True))
    idle = counts == 0
    assert idle.any() and np.abs(before[idle]).min() > 0
    assert np.array_equal(S[idle], before[idle])
    assert not o[idle].any()
    assert not np.array_equal(S[~idle], before[~idle])


def test_a_short_rows_trailing_pads_leave_its_state_where_it_was():
    """Count 3 of 8: the state after the step is the state after its
    first three positions alone."""
    xs = inputs([3, 8], 8, seed=5)
    S8, _ = gdr.gated_delta_rule(*xs, interpret=True)
    S, q, k, v, g, beta, count = xs
    S3, _ = gdr.gated_delta_rule(
        S, *(t[:, :3] for t in (q, k, v, g, beta)), count, interpret=True)
    assert np.array_equal(np.asarray(S8)[0], np.asarray(S3)[0])


@pytest.mark.parametrize("s", [1, 8])
def test_no_live_row_returns_the_input(s):
    xs = inputs([0, 0, 0], s, seed=3)
    S, o = gdr.gated_delta_rule(*xs, interpret=True)
    assert np.array_equal(np.asarray(S), np.asarray(xs[0]))
    assert not np.asarray(o).any()


def test_state_stays_float32_and_shapes_are_the_ops():
    xs = inputs([2, 0], 2)
    S, o = gdr.gated_delta_rule(*xs, interpret=True)
    assert S.dtype == o.dtype == jnp.float32
    assert S.shape == (2, HEADS, DK, DV) and o.shape == (2, 2, HEADS, DV)


@pytest.mark.parametrize("count, rows, n", [
    ([0, 2, 0, 1], [1, 3, 3, 3], 2),
    ([1, 1, 1], [0, 1, 2], 3),
    ([0, 0, 0], [2, 2, 2], 0),
    ([0, 0, 5], [2, 2, 2], 1),
])
def test_live_rows_compacts_and_repeats_the_last(count, rows, n):
    got_rows, got_n = gdr.live_rows(jnp.asarray(count, jnp.int32))
    assert got_rows.tolist() == rows and got_n.tolist() == [n]
    assert got_rows.dtype == got_n.dtype == jnp.int32


@pytest.mark.parametrize("heads, s, want", [
    (32, 1, 32), (32, 8, 16), (32, 16, 8), (4, 4, 4), (6, 8, 6),
    (12, 16, 6), (5, 16, 5), (7, 16, 7), (9, 16, 3),
])
def test_heads_per_block_divides_the_heads_inside_one_lane_tile(
        heads, s, want):
    hb = gdr.heads_per_block(heads, s)
    assert hb == want and heads % hb == 0 and hb * s <= 128


def test_heads_per_block_keeps_the_state_block_inside_default_vmem():
    """A block of state is at most 2 MB (in, out, double-buffered: 8 of
    the 16 MiB a kernel gets without asking; asking for more hung the
    served programs on the chip, PR 35)."""
    assert gdr.heads_per_block(32, 1, 128 * 128 * 4) == 32
    assert gdr.heads_per_block(32, 1, 256 * 128 * 4) == 16
    assert gdr.heads_per_block(32, 1, 256 * 256 * 4) == 8
    assert gdr.heads_per_block(32, 8, 256 * 256 * 4) == 8


# -- the ops that take the training kernels ------------------------------------
def as_on_a_tpu(monkeypatch, module):
    """`module`'s ops ask `pick_recurrence` as a TPU would; the kernels
    they then take still run interpreted (jax's backend is the CPU)."""
    monkeypatch.setattr(
        module, "pick_recurrence",
        lambda backend, *a: gdr.pick_recurrence("tpu", *a))


def plans_agree(monkeypatch, module, op, seq, embed):
    """`op`'s forward and gradient (input and every weight) under the
    CPU's plan and under a TPU's answer, which must be `chunked` and
    `chunked_kernel`."""
    @jax.jit
    def drawn(key):
        keys = jax.random.split(key, len(op.weight_specs) + 1)
        return jax.random.normal(keys[-1], (1, seq, embed)), [
            0.3 * jax.random.normal(k, [d.size for d in spec.shape.dims
                                        if not d.is_replica_dim])
            for k, spec in zip(keys, op.weight_specs)]

    x, w = drawn(jax.random.key(13))

    def run():
        return jax.jit(jax.value_and_grad(lambda x, w: jnp.sum(jnp.sin(
            op.forward([x], w, training=True)[0])), argnums=(0, 1)))(x, w)

    assert op.recurrence_plan(seq) == "chunked"
    want = run()
    as_on_a_tpu(monkeypatch, module)
    assert op.recurrence_plan(seq) == "chunked_kernel"
    # float32 sums in another order, on leaves as small as A_log's
    for a, b in zip(jax.tree.leaves(run()), jax.tree.leaves(want)):
        close(a, b, 1e-4)


def test_kda_op_takes_the_chunk_kernels_where_pick_recurrence_says_so(
        monkeypatch):
    """A TPU's answer for heads of one 128-lane tile and a row of a
    chunk or more: the op runs the Pallas walk with `pick_chunk`'s
    lengths and agrees with the jax.numpy rule, forward and gradient;
    shorter rows keep the jax.numpy rule, and `chunk_tokens` reports
    the chunk under both plans."""
    ff = FFModel(FFConfig(batch_size=1, num_devices=1))
    op = ff.kimi_delta_attention(
        ff.create_tensor([1, 80, 32], name="x"),
        KimiDeltaAttentionParams(embed_dim=32, num_heads=2, head_dim=128),
        name="op").owner_op
    calls = []
    monkeypatch.setitem(
        kda_op.CHUNKED_RULES, "chunked_kernel",
        lambda *a, **kw: calls.append(a[-2:]) or
        cdk.delta_rule_chunked_kernel(*a, **kw))
    assert op.chunk_tokens(80) == 64
    plans_agree(monkeypatch, kda_op, op, 80, 32)
    assert calls == [(64, 16)]
    assert op.chunk_tokens(80) == 64
    assert (op.recurrence_plan(40), op.chunk_tokens(40)) == ("chunked", 48)



def test_kda_on_the_kernel_plan_never_forms_a_by_head_tensor(monkeypatch):
    """On the kernel plan the op hands XLA no `[b, s, h, d]` at all,
    forward or backward (on the chip that reshape of a float32 tensor
    is a copy): q~, k~, v, g go to the operands' kernels flat as the
    convs and the decay projection leave them, their gradients come
    back so, and `o`, the head norm and the gate stay `[b, s, h d]`."""
    b, s, h, d, e = 1, 128, 2, 128, 32
    ff = FFModel(FFConfig(batch_size=b, num_devices=1))
    op = ff.kimi_delta_attention(
        ff.create_tensor([b, s, e], name="x"),
        KimiDeltaAttentionParams(embed_dim=e, num_heads=h, head_dim=d),
        name="op").owner_op
    as_on_a_tpu(monkeypatch, kda_op)
    assert op.recurrence_plan(s) == "chunked_kernel"
    w = [jnp.ones([dim.size for dim in spec.shape.dims
                   if not dim.is_replica_dim]) for spec in op.weight_specs]
    jaxpr = jax.make_jaxpr(jax.grad(lambda x, w: jnp.sum(
        op.forward([x], w, training=True)[0]), argnums=(0, 1)))(
            jnp.ones((b, s, e)), w)
    flat, kernels = (b, s, h * d), {}
    for eqn in equations(jaxpr.jaxpr, into_kernels=False):
        if eqn.primitive.name == "pallas_call":
            kernels[eqn.params["name"]] = eqn
            continue
        for v in eqn.outvars:
            # (weight-sized `[h, d]`, A_log over a head's channels, is none)
            assert len(v.aval.shape) < 3 or v.aval.shape[-2:] != (h, d), eqn
    # (the backward kernels were reached: the walk went through both)
    fwd, bwd, walk, back = (kernels[f"delta_rule_{n}"] for n in (
        "operands_fwd", "operands_bwd", "chunks_fwd", "chunks_bwd"))
    assert [v.aval.shape for v in fwd.invars[:4]] == [flat] * 4
    assert [v.aval.shape for v in bwd.invars[:4]] == [flat] * 4
    assert [v.aval.shape for v in bwd.outvars[:4]] == [flat] * 4
    assert walk.outvars[1].aval.shape == back.invars[-1].aval.shape == flat


def test_stateless_gated_delta_net_shares_the_chunk_kernels(monkeypatch):
    """One decay a head, broadcast by the wrapper as the jax.numpy rule
    broadcasts it: the same kernels, no test of the op's name."""
    p = gdn_op.GatedDeltaNetParams(embed_dim=16, num_k_heads=1,
                                   num_v_heads=2, head_k_dim=128,
                                   head_v_dim=128)
    ff = FFModel(FFConfig(batch_size=1, num_devices=1))
    op = ff.gated_delta_net(ff.create_tensor([1, 70, 16], name="x"), p,
                            name="op").owner_op
    plans_agree(monkeypatch, gdn_op, op, 70, 16)


#: (backend, slot state, head_k_dim, head_v_dim, step tokens) -> path
PICKS = [
    (("tpu", True, 128, 128, 1), "kernel"),    # the cell's decode step
    (("tpu", True, 128, 128, 8), "kernel"),    # its prefill chunk
    (("tpu", True, 256, 128, 4), "kernel"),
    (("tpu", True, 128, 128, gdr.MAX_STEP_TOKENS), "kernel"),
    (("tpu", True, 128, 128, gdr.MAX_STEP_TOKENS + 1), "plain"),
    (("tpu", True, 128, 128, 512), "plain"),
    # stateless (what a trainer differentiates) runs a chunk at a time:
    # the Pallas walk over the chunks on a TPU, with head dims of whole
    # 128-lane tiles and a row of at least one full chunk of 64 ...
    (("tpu", False, 128, 128, 8192), "chunked_kernel"),  # the KDA cell
    (("tpu", False, 128, 128, 64), "chunked_kernel"),
    (("tpu", False, 256, 128, 4096), "chunked_kernel"),
    (("tpu", False, 128, 256, 100), "chunked_kernel"),
    # ... and the jax.numpy rule everywhere else: short rows, head dims
    # that are no whole tiles (the toy models' 8), every other backend
    (("tpu", False, 128, 128, 63), "chunked"),
    (("tpu", False, 128, 128, 1), "chunked"),
    (("tpu", False, 128, 128, 0), "chunked"),
    (("tpu", False, 8, 8, 8192), "chunked"),
    (("tpu", False, 64, 128, 8192), "chunked"),
    (("tpu", False, 128, 192, 8192), "chunked"),
    (("cpu", False, 128, 128, 8192), "chunked"),
    (("gpu", False, 128, 128, 8192), "chunked"),
    (("cpu", False, 128, 128, 8), "chunked"),
    (("cpu", False, 8, 8, 16), "chunked"),
    # a CPU (and anything that is not a TPU) keeps the plain path
    (("cpu", True, 128, 128, 1), "plain"),
    (("cpu", True, 128, 128, 8), "plain"),
    (("gpu", True, 128, 128, 8), "plain"),
    # head dims that are not whole 128-lane tiles (the toy model's 8)
    (("tpu", True, 8, 8, 4), "plain"),
    (("tpu", True, 64, 128, 1), "plain"),
    (("tpu", True, 128, 192, 1), "plain"),
]


@pytest.mark.parametrize("args, want", PICKS,
                         ids=["-".join(map(str, a)) for a, _ in PICKS])
def test_pick_recurrence_is_a_pure_function_of_what_it_is_given(
        args, want, monkeypatch):
    assert gdr.pick_recurrence(*args) == want
    # the process's own backend is not read
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gdr.pick_recurrence(*args) == want


@pytest.mark.parametrize("slot_state, tokens, want", [
    (True, 1, "plain"), (False, 8192, "chunked")])
def test_pick_recurrence_without_pallas_takes_no_kernel(
        slot_state, tokens, want, monkeypatch):
    monkeypatch.setattr(gdr, "_HAVE_PALLAS", False)
    assert gdr.pick_recurrence("tpu", slot_state, 128, 128, tokens) == want


def test_kernel_never_interpreted_on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="must run compiled"):
        gdr.gated_delta_rule(*inputs([1], 1), interpret=True)
