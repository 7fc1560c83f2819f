"""The chunked delta rule with a decay per channel
(ops/chunked_delta_rule.py, what kimi_linear's KDA layers run a chunk
at a time) against `delta_rule_scan`, a position at a time: output,
final state and the gradients of q, k, v, g, beta (the Pallas kernels
of the same rule: tests/test_chunked_delta_kernels.py; the ops that
take them: tests/test_gated_delta_rule.py; the ops and the model:
tests/test_kimi_linear_ops.py, tests/test_kimi_linear.py).

Tolerances as tests/test_lfm2_moe_ops.py: the same float32 arithmetic
in another order.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _family import close, probed

from flexflow_tpu.ops import chunked_delta_rule as cdr
from flexflow_tpu.ops import kimi_delta_attention as kda_op
from flexflow_tpu.ops.gated_delta_net import delta_rule_scan, l2norm


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
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


@functools.lru_cache(maxsize=None)
def scanned_side(flat):
    def scanned(S, q, k, v, g, beta):
        if flat:
            by_head = q.shape[:2] + S.shape[1:3]
            q, k = (t.reshape(by_head) for t in (q, k))
            q, k = l2norm(q) * by_head[-1] ** -0.5, l2norm(k)
            if g.shape[2] != beta.shape[2]:  # a decay a channel
                g = g.reshape(by_head)
        S, o = delta_rule_scan(S, q, k, v.reshape(q.shape[:3] + (-1,)), g,
                               beta)
        return S, o.reshape(v.shape)

    return probed(scanned)


@functools.lru_cache(maxsize=None)
def chunked_side(chunk, sub, flat):
    def chunked(S, q, k, v, g, beta):
        if flat:  # (one decay a head: repeated, as `GatedDeltaNet` does)
            if g.shape[2] == beta.shape[2]:
                g = jnp.repeat(g, S.shape[2], axis=2)
            return kda_op.CHUNKED_RULES["chunked"](S, q, k, v, g, beta,
                                                   chunk, sub)
        return cdr.delta_rule_chunked(S, q, k, v, g, beta, chunk=chunk,
                                      sub=sub)

    return probed(chunked)


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
    """Each side is one program a shape (`probed`): the two strengths
    of a case share both."""
    xs, probes = recurrence_inputs(seq, decay == "per_channel", strong)
    if flat:  # unit rows no more, and no head axis (o as v comes)
        flatten = lambda t: t.reshape(t.shape[:2] + (-1,))  # noqa: E731
        probes = (flatten(probes[0]), probes[1])
        xs.update({n: flatten(3.0 * xs[n] if n in "qk" else xs[n])
                   for n in "qkv" + "g" * (xs["g"].ndim == 4)})
    if strong and chunk > 1:
        total = np.cumsum(np.asarray(xs["g"], np.float64), axis=1)
        with np.errstate(over="ignore"):
            assert np.isinf(np.exp(-total[:, :chunk]).astype(np.float32)).any()
    (_, (s_want, o_want)), g_want = scanned_side(flat)(xs, *probes)
    (_, (s_got, o_got)), g_got = chunked_side(chunk, sub, flat)(xs, *probes)
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
    _, want = jax.jit(delta_rule_scan)(*args)
    _, got = jax.jit(lambda *a: cdr.delta_rule_chunked(
        *a, chunk=64, sub=16, operand_dtype=jnp.bfloat16))(*args)
    close(got, want, 3e-2)


@pytest.mark.parametrize("tokens, want", [
    (8192, (64, 16)), (64, (64, 16)), (63, (64, 16)), (16, (16, 16)),
    (24, (32, 16)), (5, (5, 5)), (1, (1, 1))])
def test_pick_chunk_is_whole_sub_chunks_of_the_step(tokens, want):
    assert cdr.pick_chunk(tokens) == want

