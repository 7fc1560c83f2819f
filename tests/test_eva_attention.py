"""`EvaAttention` on the whole-sequence graph, CPU float32, toy widths
(window 16, chunk 4, 2 heads of 16): the op's forward against the plain
reference of `benchmarks/families/evabyte.py` (which shares no code
with `flexflow_tpu/ops/eva_attention.py`) over lengths on both sides of
a window's end; the summaries alone against `eva_prep_kv`'s equation
written out with numpy; all heads of the model's logits against the
reference; and the step with per-slot state against the whole-sequence
op, state and output both.

OP_TOL is 1e-5 of the largest magnitude: float32 sums of a few hundred
terms in another order.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _family import close, config
from benchmarks import reference as ref
from benchmarks.families import evabyte as fam
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.evabyte import build_evabyte
from flexflow_tpu.ops.eva_attention import (EvaAttention, EvaAttentionParams,
                                            summarise)

CFG = config("toy-evabyte.json")
D = fam.dims(CFG)
SEED = 11
KEY = ref.seed_key(SEED)
OP_TOL = 1e-5
PARAMS = EvaAttentionParams(embed_dim=D.e, num_heads=D.h, head_dim=D.hd,
                            window_size=D.w, chunk_size=D.c,
                            rope_theta=D.theta)
#: inside the first window; exactly a window; one past; three windows
#: and a partial chunk
LENGTHS = (9, 16, 17, 50)


def attn_weights():
    """One layer's attention leaves, float32 (the toy configuration
    draws `phi` and `mu` as wide as the keys, `adaptive_init_std` 1, so
    that the pooling is far from uniform)."""
    return fam.make_leaves(KEY, D, "attn", 0)


def op_at(seq, batch=1, **state):
    ff = FFModel(FFConfig(batch_size=batch, num_devices=1))
    x = ff.create_tensor([batch, seq, D.e], name="x")
    return ff.eva_attention(x, PARAMS, name="op", **state).owner_op


def reference_attention(x, w):
    """The family's layer with the norms' gains at zero offset undone,
    the MLP cut off: the attention's own output for x [s, e]."""
    s = x.shape[0]
    nw = -(-s // D.w)
    x = jnp.pad(x, ((0, nw * D.w - s), (0, 0)))
    K = jnp.zeros((D.p // D.c, D.h, D.hd))
    V = jnp.zeros_like(K)
    ident = lambda v: v  # noqa: E731
    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(nw):
            xb = x[i * D.w:(i + 1) * D.w]
            heads = lambda v: v.reshape(-1, D.h, D.hd)  # noqa: E731
            qh = fam.rotate(heads(xb @ w["wq"]), i * D.w, D)
            kh = fam.rotate(heads(xb @ w["wk"]), i * D.w, D)
            vh = heads(xb @ w["wv"])
            seen = i * (D.w // D.c)
            o = fam.attend(qh, kh, vh, K, V, seen, D, ident)
            out.append(o.reshape(-1, D.e) @ w["wo"])
            Kw, Vw = fam.pooled(kh, vh, w["adaptive_phi"],
                                w["adaptive_mu_k"], D, ident)
            K = jax.lax.dynamic_update_slice_in_dim(K, Kw, seen, 0)
            V = jax.lax.dynamic_update_slice_in_dim(V, Vw, seen, 0)
    return jnp.concatenate(out)[:s]


@pytest.mark.parametrize("seq", LENGTHS)
def test_whole_sequence_forward_equals_the_reference(seq):
    w = attn_weights()
    x = jax.random.normal(jax.random.key(seq), (2, seq, D.e))
    op = op_at(seq, batch=2)
    names = [s.name for s in op.weight_specs]
    assert names == list(fam.leaf_shapes(D, "attn"))
    got = jax.jit(lambda x: op.forward([x], [w[n] for n in names])[0])(x)
    want = jnp.stack([reference_attention(row, w) for row in x])
    close(got, want, OP_TOL)


def test_summaries_alone_equal_the_pooling_equation():
    """`eva_prep_kv`: a_j = softmax_j(s <k_j, phi>), K = sum a_j k_j +
    mu, V = sum a_j v_j, a chunk of 4 at a time, in numpy."""
    rng = np.random.default_rng(3)
    k, v = rng.normal(size=(2, 5, D.c, D.h, D.hd)).astype(np.float32)
    phi, mu = rng.normal(size=(2, D.h, D.hd)).astype(np.float32)
    scale = D.hd ** -0.5
    z = scale * np.einsum("mjhd,hd->mjh", k.astype(np.float64), phi)
    a = np.exp(z - z.max(axis=1, keepdims=True))
    a /= a.sum(axis=1, keepdims=True)
    K, V = summarise(jnp.asarray(k), jnp.asarray(v), jnp.asarray(phi),
                     jnp.asarray(mu), scale)
    close(K, np.einsum("mjh,mjhd->mhd", a, k) + mu, OP_TOL)
    close(V, np.einsum("mjh,mjhd->mhd", a, v), OP_TOL)
    # and the reference's own pooling says the same
    with jax.default_matmul_precision("highest"):
        Kr, Vr = fam.pooled(jnp.asarray(k).reshape(-1, D.h, D.hd),
                            jnp.asarray(v).reshape(-1, D.h, D.hd),
                            jnp.asarray(phi), jnp.asarray(mu), D,
                            lambda t: t)
    close(K, Kr, OP_TOL)
    close(V, Vr, OP_TOL)


@pytest.mark.parametrize("head", range(CFG["num_pred_heads"]))
def test_every_head_of_the_models_logits_equals_the_reference(head, logits):
    got, want = logits
    assert got.shape == (50, D.v * D.heads_out)
    close(got[:, head * D.v:(head + 1) * D.v],
          want[:, head * D.v:(head + 1) * D.v], 2e-5)


@pytest.fixture(scope="module")
def logits():
    """The whole model over 50 positions (four windows): the program's
    logits, every prediction head, and the reference's."""
    ff = FFModel(FFConfig(batch_size=1, num_devices=1,
                          compute_dtype="float32"))
    build_evabyte(ff, 1, 50, **fam.published(CFG))
    ff.compile(devices=jax.devices()[:1], defer_weights=True)
    ff.set_weights(fam.make_weights(CFG, SEED, "program"))
    ids = np.random.default_rng(2).integers(1, D.v, (1, 50)).astype(np.int32)
    want = fam.logits_fn(fam.make_weights(CFG, SEED, "reference"), ids[0],
                         "float32")
    return np.asarray(ff.forward({"input": ids}))[0], np.asarray(want)


# -- the step with per-slot state against the whole-sequence op -------------------
def run_in_steps(x, w, steps, slots=3, row=1):
    """x [s, e] fed to slot `row` of a `slots`-slot op in steps of the
    given lengths (each its own op: the step's length is its input's),
    the other slots idle: (outputs [s, e], the final state)."""
    names = list(fam.leaf_shapes(D, "attn"))
    state, out, at = None, [], 0
    for n in steps:
        op = op_at(n, batch=slots, slot_state=True, max_seq=D.p)
        assert isinstance(op, EvaAttention)
        if state is None:
            # garbage in every array: nothing of it may be read
            state = [jnp.full(s.shape.logical_shape, 7.0)
                     for s in op.weight_specs[6:12]]
        xs = jnp.zeros((slots, n, D.e)).at[row].set(x[at:at + n])
        lens = jnp.zeros(slots, jnp.int32).at[row].set(at)
        fed = jnp.zeros(slots, jnp.int32).at[row].set(n)
        got = jax.jit(lambda xs, state, lens, fed, op=op: op.forward(
            [xs], [w[k] for k in names] + state + [lens, fed]))(
                xs, state, lens, fed)
        out.append(got[0][row])
        state = list(got[1:7])
        at += n
    return jnp.concatenate(out), state


@pytest.mark.parametrize("steps", [
    [1] * 22,            # a token a step across a window's end
    [6, 1, 6, 1, 6, 1],  # the scheduler's pairs: a pass, then a step
    [3, 10, 12, 7, 1],   # passes that cross chunk and window ends anywhere
    [16, 16, 2],         # whole windows
], ids=["ones", "pairs", "ragged", "windows"])
def test_steps_through_the_state_equal_the_whole_sequence(steps):
    w = attn_weights()
    s = sum(steps)
    x = jax.random.normal(jax.random.key(5), (s, D.e))
    want = reference_attention(x, w)
    got, state = run_in_steps(x, w, steps)
    close(got, want, OP_TOL)
    win_k, _, sum_k, _, pend_k, _ = state
    # the store holds the summary of every chunk that ended, and the
    # slots that never advanced still hold what they were given
    assert not np.any(np.asarray(sum_k[1, :s // D.c]) == 7.0)
    assert np.all(np.asarray(sum_k[1, s // D.c:]) == 7.0)
    for idle in (0, 2):
        for arr in (win_k, sum_k, pend_k):
            assert np.all(np.asarray(arr[idle]) == 7.0)


def test_a_row_that_does_not_advance_keeps_its_state_to_the_byte():
    """`row_tokens` 0 (an idle slot, a rider of a prefill pass): every
    array of the row stays as it was, whatever the step's tokens."""
    w = attn_weights()
    x = jax.random.normal(jax.random.key(8), (12, D.e))
    _, before = run_in_steps(x, w, [5, 7])
    op = op_at(4, batch=3, slot_state=True, max_seq=D.p)
    names = list(fam.leaf_shapes(D, "attn"))
    got = op.forward(
        [jnp.ones((3, 4, D.e))],
        [w[k] for k in names] + before
        + [jnp.asarray([0, 12, 0], jnp.int32), jnp.zeros(3, jnp.int32)])
    for a, b in zip(before, got[1:7]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_state_and_scope_names():
    from flexflow_tpu.obs import scopes

    op = op_at(1, batch=2, slot_state=True, max_seq=D.p)
    assert op.slot_state_entries() == (
        "win_k", "win_v", "sum_k", "sum_v", "pend_k", "pend_v")
    assert op.cache_entries() == () and op.slot_state_resets is False
    shapes = {s.name: tuple(s.shape.logical_shape)
              for s in op.weight_specs[6:]}
    assert shapes == {
        "win_k": (2, D.w, D.h, D.hd), "win_v": (2, D.w, D.h, D.hd),
        "sum_k": (2, D.p // D.c, D.h, D.hd),
        "sum_v": (2, D.p // D.c, D.h, D.hd),
        "pend_k": (2, D.c, D.h, D.hd), "pend_v": (2, D.c, D.h, D.hd),
        "seq_lens": (2,), "row_tokens": (2,)}
    assert op_at(8).slot_state_entries() == ()
    for part in ("proj", "summarise", "core", "state_write", "out"):
        assert part in scopes.PARTS
    parsed = scopes.parse("jit(step)/EvaAttention:attn_3/summarise/add")
    assert (parsed.program, parsed.kind, parsed.name, parsed.part) == (
        "step", "EvaAttention", "attn_3", "summarise")


def test_shapes_the_op_refuses():
    from flexflow_tpu.ops.op import ShapeError

    with pytest.raises(ShapeError, match="must divide"):
        ff = FFModel(FFConfig(batch_size=1, num_devices=1))
        x = ff.create_tensor([1, 4, D.e], name="x")
        ff.eva_attention(x, EvaAttentionParams(D.e, D.h, D.hd, 16, 5))
    with pytest.raises(ShapeError, match="a step of 1..window_size"):
        op_at(D.w + 1, slot_state=True, max_seq=D.p)
