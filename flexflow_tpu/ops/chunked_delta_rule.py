"""The gated delta rule a CHUNK of positions at a time: what the
stateless (training) shape of `GatedDeltaNet` and `KimiDeltaAttention`
runs, forward and backward, wherever `pick_recurrence` answers
"chunked": every backend but the TPU, and on a TPU the rows under one
full chunk and the head dims that are no whole 128-lane tiles.  (Where
it answers "chunked_kernel", `ops/pallas/chunked_delta_rule.py` runs
the same algebra as Pallas kernels, with this file's functions as
their oracle.)  Both ops reach it through `delta_rule_chunked_plain`,
the table's signature and layout: the convs' q~, k~ and v, g flat, a
head a block of channels, and `l2norm` (here too: one formula and eps
for serving, training and the kernels' tiles) the rule's.

    per head, S a [dk, dv] matrix, for each position in order:
        S' = Diag(exp(g_t)) S;  d_t = beta_t (v_t - S'^T k_t)
        S = S' + k_t d_t^T;     o_t = S^T q_t          g_t in R^dk, <= 0

(`ops/gated_delta_net.py delta_rule_step` is one position of it; Gated
DeltaNet's `g` is one number a head, broadcast over the dk channels
here.)  A `lax.scan` a position keeps every position's `S` for its
backward pass: 8,192 x 32 heads x 64 KB = 17 GB a layer.

Inside a chunk of C positions, with G_t the decays' running sum from
the chunk's start (`G_t = g_1 + .. + g_t`, per channel) and S_0 the
state the chunk starts from, the recurrence unrolls to

    A_ti = sum_c k_tc k_ic exp(G_tc - G_ic)   (i <  t)   keys against keys
    B_ti = sum_c q_tc k_ic exp(G_tc - G_ic)   (i <= t)   queries against keys
    (I + Diag(beta) A) D = Diag(beta) (V - (exp(G) . K) S_0)
        =>  D = U - W S_0,  U = T (beta V),  W = T (beta exp(G) . K),
            T = (I + Diag(beta) A)^-1         a unit lower-triangular solve
    O   = (exp(G) . Q) S_0 + B D
    S_C = Diag(exp(G_C)) S_0 + (exp(G_C - G) . K)^T D

so everything that does not touch S_0 (A, B, U, W and the scaled q and
k) is computed for ALL chunks at once, as batched products, and a
`lax.scan` over the chunks carries `S` through three products a chunk.
Its backward pass (jax's own: the body is plain jax.numpy under
`jax.checkpoint`) keeps the chunk-boundary states only: s / C x h x dk
x dv x 4 B, 268 MB a layer at 8,192 positions and C = 64.

**The decays' range.**  The textbook form folds `exp(G_t)` into q and
`exp(-G_i)` into k; `exp(-G_i)` overflows float32 once a channel has
decayed by e^88 inside a chunk, which a learned per-channel decay is
free to do.  Nothing here ever exponentiates a positive number:

* the chunk is cut into sub-chunks of `sub` positions.  Inside one, the
  `exp(G_t - G_i)` of every pair i <= t is formed directly (a
  `[sub, sub, dk]` block, exponents <= 0 by construction);
* for a pair in two different sub-chunks, with R the running sum at
  the end of the sub-chunk BEFORE t's, `exp(G_t - R) <= 1` goes with
  the row and `exp(R - G_i) <= 1` with the column, and the block is a
  matrix product.

A decay strong enough to underflow simply reads 0, which is what the
position-at-a-time rule computes too.  `Q exp(G)` and `K exp(G_C - G)`
are <= 1 times their operand as they stand.

Precision.  The state, the running sums, A, B and the triangular solve
are float32 (products at `HIGHEST`); the products with S inside the
scan and B D take their operands in `operand_dtype` (the op's compute
precision: bf16 on the chip, where the MXU runs them in one pass;
float32 in the CPU tests, where the whole function equals
`delta_rule_scan` to rounding) and accumulate in float32.

A form that was tried and taken out (PERF.md section 6, PR 43): the
chunk as a LINEAR map of S_0 (`S_C = shrink . S_0 - M S_0 + N`, `O = P
S_0 + R` with M, N, P, R batched over all chunks), whose scan carries
one `[dk, dk] x [dk, dv]` product and recomputes nothing.  On the chip
it read 58.4 ms a layer forward + backward against this form's 48.7:
the state-sized products and their float32 residuals (M, N and the
boundary states, 268 MB each a layer, with their gradients) cost more
than the three chunk-sized products of the scan save.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST

#: positions a chunk (one step of the scan over chunks) and a sub-chunk
#: (the block whose pairwise decays are formed directly) hold where the
#: sequence is long enough; `pick_chunk` says what a step length gets.
#: scripts/kda_core_probe.py measures the choice (PERF.md section 6,
#: PR 43: a layer's core alone is 10 % faster at 32 and slower at 128,
#: but at 32 the whole training step of the benchmark's cell no longer
#: fits the chip with its matrix products kept under `remat`)
CHUNK_TOKENS = 64
SUB_CHUNK_TOKENS = 16


#: the eps under `l2norm`'s root: one number for serving and training,
#: jax.numpy and the kernels' tiles
L2NORM_EPS = 1e-6


def row_rsqrt(x, eps: float = L2NORM_EPS):
    """1 / sqrt(the sum of squares of each row of the last axis + eps)."""
    return jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def l2norm(x, eps: float = L2NORM_EPS):
    """Each row of the last axis over its length: what both delta-rule
    ops do to a head's q and k, in jax.numpy and (the same function on
    a resident tile) in `ops/pallas/chunked_delta_rule.py`'s kernels."""
    return x * row_rsqrt(x, eps)


def unit_heads(q, k):
    """What both delta-rule ops feed the rule, from their convs' q~, k~
    (a head's channels the last axis): q = l2norm(q~) / sqrt(dk),
    k = l2norm(k~)."""
    return l2norm(q) * q.shape[-1] ** -0.5, l2norm(k)


def pick_chunk(step_tokens: int) -> tuple:
    """(chunk, sub-chunk) positions for a step of `step_tokens` tokens a
    row: a pure function of the length.  A row shorter than a chunk is
    one chunk of whole sub-chunks."""
    if step_tokens >= CHUNK_TOKENS:
        return CHUNK_TOKENS, SUB_CHUNK_TOKENS
    sub = min(SUB_CHUNK_TOKENS, max(step_tokens, 1))
    return -(-step_tokens // sub) * sub, sub


def _pairwise_blocks(q, k, G):
    """The sub-chunk diagonal: q, k, G [..., m, c, dk] -> (A, B)
    [..., m, c, c], A_ti for i < t and B_ti for i <= t of the same
    sub-chunk, zero elsewhere.  The exponent is masked BEFORE `exp`
    (and after), so neither the value nor its gradient ever sees a
    positive one."""
    c = q.shape[-2]
    t, i = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    diff = G[..., :, None, :] - G[..., None, :, :]  # [..., t, i, dk]
    low = (t >= i)[..., None]
    decay = jnp.where(low, jnp.exp(jnp.where(low, diff, 0.0)), 0.0)
    kk = jnp.sum(k[..., :, None, :] * k[..., None, :, :] * decay, axis=-1)
    qk = jnp.sum(q[..., :, None, :] * k[..., None, :, :] * decay, axis=-1)
    return jnp.where(t > i, kk, 0.0), qk


def _chunk_matrices(q, k, G, sub: int):
    """q, k, G [..., C, dk] float32 -> (A strictly lower, B lower)
    [..., C, C] float32 (module docstring)."""
    C, dk = q.shape[-2:]
    m = C // sub
    lead = q.shape[:-2]
    blocks = [t.reshape(lead + (m, sub, dk)) for t in (q, k, G)]
    a_diag, b_diag = _pairwise_blocks(*blocks)
    rows_a, rows_b = [], []
    for j in range(m):
        lo, hi = j * sub, (j + 1) * sub
        right = jnp.zeros(lead + (sub, C - hi), jnp.float32)
        if j == 0:
            rows_a.append(jnp.concatenate([a_diag[..., 0, :, :], right], -1))
            rows_b.append(jnp.concatenate([b_diag[..., 0, :, :], right], -1))
            continue
        base = G[..., lo - 1:lo, :]  # the sum at the end of sub-chunk j - 1
        row = jnp.exp(G[..., lo:hi, :] - base)  # <= 1
        col = k[..., :lo, :] * jnp.exp(base - G[..., :lo, :])  # <= 1
        a_off = jnp.einsum("...tc,...ic->...ti", k[..., lo:hi, :] * row, col,
                           precision=_HIGHEST)
        b_off = jnp.einsum("...tc,...ic->...ti", q[..., lo:hi, :] * row, col,
                           precision=_HIGHEST)
        rows_a.append(jnp.concatenate(
            [a_off, a_diag[..., j, :, :], right], -1))
        rows_b.append(jnp.concatenate(
            [b_off, b_diag[..., j, :, :], right], -1))
    return jnp.concatenate(rows_a, -2), jnp.concatenate(rows_b, -2)


def delta_rule_chunked(S, q, k, v, g, beta, chunk: int, sub: int,
                       operand_dtype=jnp.float32):
    """The recurrence of the module docstring over a step's positions:
    S [b, h, dk, dv] float32, q / k [b, s, h, dk], v [b, s, h, dv],
    g [b, s, h] (one decay a head) or [b, s, h, dk] (one a channel),
    beta [b, s, h] -> (S, o [b, s, h, dv] float32).  `chunk` a multiple
    of `sub`; a length that is no multiple of `chunk` is padded with
    positions that leave the state as it was (beta = 0, g = 0)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    if chunk % sub:
        raise ValueError(f"chunk {chunk} is no multiple of sub-chunk {sub}")
    f32 = jnp.float32
    if g.ndim == 3:
        g = jnp.broadcast_to(g[..., None], (b, s, h, dk))
    n = -(-s // chunk)

    def chunks(t):  # [b, s, h, d...] -> [n, b, h, chunk, d...]
        t = t.astype(f32)
        t = jnp.pad(t, ((0, 0), (0, n * chunk - s)) + ((0, 0),) * (t.ndim - 2))
        t = t.reshape((b, n, chunk) + t.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(t, 1, 0), 2, 3)

    q, k, v, g = map(chunks, (q, k, v, g))
    beta = chunks(beta[..., None])  # [n, b, h, chunk, 1]
    G = jnp.cumsum(g, axis=-2)
    A, B = _chunk_matrices(q, k, G, sub)
    system = jnp.eye(chunk, dtype=f32) + beta * A
    decay = jnp.exp(G)
    solved = jax.scipy.linalg.solve_triangular(
        system, jnp.concatenate([beta * v, beta * decay * k], -1),
        lower=True, unit_diagonal=True)
    exact = jnp.dtype(operand_dtype) == jnp.dtype(f32)
    prec = _HIGHEST if exact else None

    def op(t):
        return t.astype(operand_dtype)

    last = G[..., -1:, :]
    xs = (op(solved[..., :dv]), op(solved[..., dv:]), op(q * decay), op(B),
          op(k * jnp.exp(last - G)), jnp.exp(last[..., 0, :]))

    @jax.checkpoint
    def one_chunk(S, x):
        u, w, qg, bm, kt, shrink = x
        d = u.astype(f32) - jnp.einsum("bhtk,bhkv->bhtv", w, op(S),
                                       precision=prec,
                                       preferred_element_type=f32)
        o = (jnp.einsum("bhtk,bhkv->bhtv", qg, op(S), precision=prec,
                        preferred_element_type=f32)
             + jnp.einsum("bhti,bhiv->bhtv", bm, op(d), precision=prec,
                          preferred_element_type=f32))
        S = S * shrink[..., None] + jnp.einsum(
            "bhtk,bhtv->bhkv", kt, op(d), precision=prec,
            preferred_element_type=f32)
        return S, o

    S, o = jax.lax.scan(one_chunk, S.astype(f32), xs)
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1)  # [b, n, chunk, h, dv]
    return S, o.reshape(b, n * chunk, h, dv)[:, :s]


def delta_rule_chunked_plain(S, q, k, v, g, beta, chunk: int, sub: int,
                             operand_dtype=jnp.float32):
    """`delta_rule_chunked` behind `CHUNKED_RULES`' signature
    (`ops/pallas/chunked_delta_rule.py`), as the ops hand over: S
    [b, h, dk, dv] float32; the convs' q~, k~ `[b, s, h dk]` and v
    `[b, s, h dv]` FLAT, a head a block of channels; g `[b, s, h dk]`,
    one decay a channel; beta `[b, s, h]` -> (S, o `[b, s, h dv]`
    float32).  The rule is fed `unit_heads(q~, k~)`; the by-head form
    and the norm are jax.numpy's here (off the TPU a reshape is free)."""
    b, s = q.shape[:2]
    h = S.shape[1]
    q, k, v, g = (t.reshape(b, s, h, -1) for t in (q, k, v, g))
    S, o = delta_rule_chunked(S, *unit_heads(q, k), v, g, beta, chunk, sub,
                              operand_dtype)
    return S, o.reshape(b, s, -1)
