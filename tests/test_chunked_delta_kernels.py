"""The chunked delta rule's Pallas kernels
(ops/pallas/chunked_delta_rule.py: a chunk of positions at a time,
forward and gradient) under the interpreter against the plain
`delta_rule_scan`, their tile functions against jax's own gradient.  The
serving kernel, `pick_recurrence` and the ops that take these kernels
where it says so: tests/test_gated_delta_rule.py.

Tolerance: as there, 1e-5 of the compared tensor's largest magnitude
where a test names no other.  The scan's side of a case is computed
once for the table's entries and a jax.numpy side is one program a
shape (`_family.probed`); the tile functions stay eager (`_family`).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _family import close, probed

from flexflow_tpu.ops import chunked_delta_rule as cdr
from flexflow_tpu.ops.gated_delta_net import delta_rule_scan as scanned
from flexflow_tpu.ops.gated_delta_net import l2norm
from flexflow_tpu.ops.pallas import chunked_delta_rule as cdk


@functools.partial(jax.jit, static_argnums=(0, 1, 2),
                   static_argnames=("h",))
def recurrence_inputs(s, per_channel, strong, b=1, h=2, dk=128, dv=128):
    """As tests/test_kimi_linear_rule.py's, at heads of one 128-lane tile
    and in `CHUNKED_RULES`' layout, as the ops hand them over: q~, k~ as a
    conv leaves them (no unit rows) and v flat, `[b, s, h d]`; g one
    decay a channel, flat too, or one a head `[b, s, h]` (which
    `the_rule` repeats as `GatedDeltaNet` does)."""
    keys = jax.random.split(jax.random.key(0), 8)
    g_shape = (b, s, h * dk) if per_channel else (b, s, h)
    return dict(
        S=jax.random.normal(keys[5], (b, h, dk, dv)),
        q=jax.random.normal(keys[0], (b, s, h * dk)),
        k=jax.random.normal(keys[1], (b, s, h * dk)),
        v=jax.random.normal(keys[2], (b, s, h * dv)),
        g=-jax.nn.softplus(jax.random.normal(keys[3], g_shape))
        * (40.0 if strong else 1.0),
        beta=jax.nn.sigmoid(jax.random.normal(keys[4], (b, s, h))),
    ), (jax.random.normal(keys[6], (b, s, h * dv)),
        jax.random.normal(keys[7], (b, h, dk, dv)))


def scanned_flat(S, q, k, v, g, beta):
    """The scan a position fed what the flat hand-over means: the
    operands by head, `l2norm(q~) / sqrt(dk)` and `l2norm(k~)`."""
    (b, s), (_, h, dk, _) = q.shape[:2], S.shape
    q, k, v = (t.reshape(b, s, h, -1) for t in (q, k, v))
    if g.shape[2:] != (h,):
        g = g.reshape(b, s, h, dk)
    S, o = scanned(S, l2norm(q) * dk ** -0.5, l2norm(k), v, g, beta)
    return S, o.reshape(b, s, -1)


@functools.lru_cache(maxsize=None)
def the_rule(entry, **kw):
    """`CHUNKED_RULES[entry]` at the cell's chunk and sub-chunk; one
    decay a head is repeated over the head's channels first."""
    def rule(S, q, k, v, g, beta):
        if g.shape[2] == beta.shape[2]:
            g = jnp.repeat(g, S.shape[2], axis=2)
        return cdk.CHUNKED_RULES[entry](S, q, k, v, g, beta, 64, 16, **kw)

    return rule


compiled = functools.lru_cache(maxsize=None)(probed)


def value_and_gradient(rule, xs, probe_o, probe_s, jit=True):
    """((S, o), the gradient of a probe of both) of `rule` over the
    dict `xs`: one program a shape of its arguments (`the_rule` hands
    the cases of one entry one function), but where `rule` runs kernels
    under the interpreter (`jit=False`: each is a program already)."""
    (_, out), grads = compiled(rule, jit)(xs, probe_o, probe_s)
    return out, grads


@functools.lru_cache(maxsize=None)
def scan_case(seq, per_channel, strong):
    """(xs, probes, the scan's value and gradient): once for the
    table's entries."""
    xs, probes = recurrence_inputs(seq, per_channel, strong)
    return xs, probes, value_and_gradient(scanned_flat, xs, *probes)


@pytest.mark.parametrize("entry", list(cdk.CHUNKED_RULES))
@pytest.mark.parametrize("strong", [False, True], ids=["mild", "strong"])
@pytest.mark.parametrize("decay", ["per_channel", "per_head"])
@pytest.mark.parametrize("seq, heads_block", [
    (128, None),  # two whole chunks, both heads in one grid program
    (150, 1),     # a ragged last chunk, a head a program
])
def test_chunk_kernels_equal_the_scan_forward_and_gradient(
        seq, heads_block, decay, strong, entry):
    """The table's entries, the Pallas kernels (interpreted) and the
    jax.numpy rule behind one signature, from a NONZERO starting state,
    at the cell's chunk and heads of one 128-lane tile, against the scan
    a position fed `l2norm`ed q and k: the l2norm is the rule's (in the
    operands' kernels), and the gradients are q~'s and k~'s.  Strong: a
    channel decays past e^-88 inside a chunk; the value and the
    gradient stay finite and equal."""
    xs, probes, ((s_want, o_want), g_want) = scan_case(
        seq, decay == "per_channel", strong)
    if strong:
        total = np.cumsum(np.asarray(xs["g"], np.float64), axis=1)
        assert total[:, :64].min() < -88.0
    kernel = entry == "chunked_kernel"
    rule = the_rule(entry, **({"heads_block": heads_block} if kernel else {}))
    (s_got, o_got), g_got = value_and_gradient(rule, xs, *probes,
                                               jit=not kernel)
    close(o_got, o_want)
    close(s_got, s_want)
    for name in ("S", "q", "k", "v", "g", "beta"):
        assert np.all(np.isfinite(np.asarray(g_got[name])))
        close(g_got[name], g_want[name], 1e-4 if name == "g" else 2e-5)


@pytest.mark.parametrize("decay", ["per_channel", "per_head"])
def test_chunk_kernels_with_rounded_operands_stay_near_the_scan(decay):
    """bf16 operands (what the chip runs) through the kernels: the
    output within the jax.numpy rule's distance of the scan, and the
    gradient within a bf16 rounding of the jax.numpy rule's own (the
    table's two entries, one signature)."""
    xs, probes, ((_, o_want), _) = scan_case(150, decay == "per_channel",
                                             False)
    bf = jnp.bfloat16
    (s_jnp, o_jnp), g_jnp = value_and_gradient(
        the_rule("chunked", operand_dtype=bf), xs, *probes)
    (s_got, o_got), g_got = value_and_gradient(
        the_rule("chunked_kernel", operand_dtype=bf), xs, *probes, jit=False)
    close(o_got, o_want, 3e-2)
    close(o_got, o_jnp, 1e-2)
    close(s_got, s_jnp, 1e-2)
    for name in ("S", "q", "k", "v", "g", "beta"):
        close(g_got[name], g_jnp[name], 3e-2)


def test_chunk_kernels_refuse_the_interpreter_on_a_tpu(monkeypatch):
    xs, _ = recurrence_inputs(64, True, False, h=1)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="compiled"):
        cdk.delta_rule_chunked_kernel(
            *(xs[n] for n in "S q k v g beta".split()), 64, 16,
            interpret=True)


@pytest.mark.parametrize("heads, want", [
    (32, 8), (16, 8), (12, 6), (7, 7), (2, 2), (1, 1), (22, 2)])
def test_heads_per_program_divides_the_rows_heads(heads, want):
    """A grid program (the walk's and the operands') holds up to 8 heads
    that divide the row's; an operand's flat block is then that many
    column blocks of a head's width."""
    assert cdk.heads_per_program(heads) == want
    (spec,) = cdk._specs("p", [(1, 128, heads * 128)], want, (1, heads, 2),
                         64, False)
    assert spec.block_shape == (1, 64, want * 128)
    assert spec.index_map(0, 1, 1) == (0, 1, 1)


@pytest.mark.parametrize("strong", [False, True], ids=["mild", "strong"])
def test_pairs_tile_equals_the_chunk_matrices_and_its_gradient_is_jaxs(
        strong):
    """A head's chunk on tiles: `A`, `B` and the scaled operands against
    the jax.numpy rule's `_chunk_matrices`, and the hand-written
    gradient against jax's own of the same function."""
    C, dk, sub = 64, 128, 16
    keys = jax.random.split(jax.random.key(1), 9)
    q = l2norm(jax.random.normal(keys[0], (C, dk))) * dk ** -0.5
    k = l2norm(jax.random.normal(keys[1], (C, dk)))
    g = -jax.nn.softplus(jax.random.normal(keys[2], (C, dk))) \
        * (40.0 if strong else 1.0)
    G = jnp.cumsum(g, axis=0)
    outs = cdk._pairs_tile(q, k, G, sub)
    A, Bm = cdr._chunk_matrices(q, k, G, sub)
    for got, want in zip(outs, (A, Bm, q * jnp.exp(G), k * jnp.exp(G),
                                k * jnp.exp(G[-1:] - G), jnp.exp(G[-1:]))):
        close(got, want, 1e-5)
    cts = [jax.random.normal(key, o.shape) for key, o in zip(keys[3:], outs)]
    _, vjp = jax.vjp(lambda *a: cdk._pairs_tile(*a, sub), q, k, G)
    for got, want in zip(cdk._pairs_tile_bwd(q, k, G, sub, *cts),
                         vjp(tuple(cts))):
        assert np.all(np.isfinite(np.asarray(got)))
        close(got, want, 1e-5)


@pytest.mark.parametrize("strong", [False, True], ids=["mild", "strong"])
def test_operands_tile_gradient_is_jaxs(strong):
    """The tile that takes the convs' q~, k~, normalises them (the
    `l2norm` of the ops, q also by 1 / sqrt(dk)), scales by beta and
    builds the solve's system and right side: its value from
    `_pairs_tile` of the unit rows, and its hand-written gradient
    (q~, k~, v, g, beta; the norm's by hand, `_l2norm_bwd`) against
    jax's own; `A` is a residual and takes no cotangent."""
    C, dk, sub = 64, 128, 16
    keys = jax.random.split(jax.random.key(2), 12)
    q, k = (3.0 * jax.random.normal(key, (C, dk)) for key in keys[:2])
    v = jax.random.normal(keys[2], (C, dk))
    g = -jax.nn.softplus(jax.random.normal(keys[3], (C, dk))) \
        * (40.0 if strong else 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (C, 1)))
    (A, *outs), vjp = jax.vjp(lambda *a: cdk._operands_tile(*a, sub),
                              q, k, v, g, beta)
    a, b, qg, kg, kt, shrink = cdk._pairs_tile(
        l2norm(q) * dk ** -0.5, l2norm(k),
        jnp.matmul(cdk._tril(C), g, precision="highest"), sub)
    for got, want in zip((A, *outs), (
            a, jnp.eye(C) + beta * a, jnp.concatenate([beta * v, beta * kg], 1),
            b, qg, kt, shrink)):
        close(got, want)
    cts = [jax.random.normal(key, o.shape) for key, o in zip(keys[5:], outs)]
    for got, want in zip(
            cdk._operands_tile_bwd(q, k, v, g, beta, sub, A, *cts),
            vjp((jnp.zeros_like(A), *cts))):
        close(got, want, 1e-5)
    dy = cts[3]  # [C, dk]
    close(cdk._l2norm_bwd(q, dy), jax.vjp(l2norm, q)[1](dy)[0])
