"""The chunked delta rule as Pallas kernels (TPU), forward and backward:
what a chunk needs of its own positions is computed on tiles that
never leave VMEM, and a head's state stays in VMEM from the row's
first chunk to its last.

`ops/chunked_delta_rule.py` (its module docstring has the algebra) is
plain jax.numpy differentiated by jax.  On the chip a layer of it is
~1,400 fusions forward and backward, nearly all of them passes over
`[chunks, heads, C, dk]` float32 tensors through HBM (the sub-chunk
decay blocks' reductions and their gradients, the blocks' products,
layout changes, casts: PERF.md section 6, PR 44); the `lax.scan` over
the chunks is a small part.  Here the same algebra is three stages:

1. **the operands** (`_operands`: two kernels, `_operands_tile` and its
   hand-written gradient `_operands_tile_bwd`).  A grid program is
   (row, 8 heads, chunk) and reads q~, k~, v, g WHERE THE OP'S CONVS
   AND DECAY PROJECTION LEFT THEM, FLAT: a `[C, 8 d]` block of
   `[b, s, h d]`, of which head j's `[C, d]` is column block j, whole
   128-lane tiles (no `[b, s, h, d]` form, whose reshape of a float32
   tensor is a copy on this layout and whose blocks put a head's rows
   on one sublane of each position's tile; no `[n, b, h, C, d]` copy
   either).  It does the ops' l2norm itself, on the tile
   (`unit_heads`: `q = l2norm(q~) / sqrt(dk)`, `k = l2norm(k~)`, a
   row's norm one lane reduction; float32, `l2norm` itself), and the
   backward kernel the norm's gradient
   (`dq~ = r (dq - q^ (q^ . dq))`), so the gradients leave flat too,
   dq~, dk~, dv, dg where the convs' and the projection's backward
   read them.  It forms the running sums `G`, `A` and `B`
   (`_pairs_tile`: a sub-chunk's pairwise decays `exp(G_t - G_i)` a
   column at a time, masked before the exponential; the blocks left of
   a sub-chunk as one float32 product against the sub-chunk's base,
   exponents <= 0 on both sides), the scaled operands `q exp(G)`, `k exp(G_C - G)`,
   `exp(G_C)`, and the solve's system `I + Diag(beta) A` and right side
   `[beta V | beta exp(G) K]`.
2. **the solve**, XLA's: `jax.scipy.linalg.solve_triangular`, float32,
   differentiated by jax (a batched 64 x 64 solve is the one part XLA
   runs well, 2.7 ms a layer; forward substitution in a kernel is a
   chain of 64 dependent steps a head and chunk, and an inverse by
   products ~10 small float32 matrix products of six passes each).
3. **the walk** (`_walk`: two kernels), fed the solve's `[u | w]` as
   it stands in float32.  A grid program is (row, block of heads,
   chunk), the chunk innermost: the heads' `S` live in the
   program's output block, which stays in VMEM while the chunk index
   moves, and a chunk is four MXU products on resident tiles.  The
   backward kernel walks the chunks in reverse with `dS` resident.
   `o` leaves as the operands came, flat `[b, s, h dv]` (head j's
   output is column block j of the program's `[C, 8 dv]` block), and
   `do` is read so: no `[n, b, h, C, dv]` to move to `[b, s, h, dv]`.

        forward, a chunk (`~` is a cast to the operand dtype):
            d   = u - w S~            o = qg S~ + bm d~
            S'  = Diag(shrink) S + kt^T d~
        backward, from dS' and do (d recomputed from the chunk's S,
        which the forward kernel wrote out):
            dd  = bm^T do~ + kt dS'~            (= du)
            dw  = -dd~ S~^T    dqg = do~ S~^T   dbm = do~ d~^T
            dkt = d~ dS'~^T    dshrink = rowsum(S . dS')
            dS  = Diag(shrink) dS' + qg^T do~ - w^T dd~

Precision is the chunked rule's, to the letter: the state, `dS`, the
running sums, `A`, `B`, the solve and every sum in float32, float32
products at `HIGHEST` (all passes); the walk's products take their
operands in `operand_dtype` (bf16 on the chip: one MXU pass) and
accumulate in float32 (float32 operands multiply at `HIGHEST`: the CPU
tests, where the whole function equals `delta_rule_scan` to rounding).
No exponent above 0 is ever taken.

Layout of the walk.  The state is held TRANSPOSED, `[dv, dk]`: the
per-channel `shrink` is then a row `[1, dk]` that broadcasts along
sublanes (as a column it would be a strided read of 128 single
floats), and `dshrink` is a reduction over sublanes.  `w S~` and
`qg S~` are one product against the same tile (`[w; qg]`, 2C rows), as
are the two `[dk, dv]`-shaped terms of `dS` (`[do~; -dd~]^T [qg; w]`).

Residuals: of the operands, q~, k~ (before the norm), v, g, beta
themselves and `A` (everything else, the norm too, is recomputed from
them in the backward kernel); of the walk, its operands and the chunk-boundary states `[n, b, h, dv,
dk]` float32 (268 MB a layer at 8,192 positions of 32 heads: what
jax's own backward of the scan kept).  Nothing is tagged `remat_keep`:
under the executor's `remat` the forward kernels and the solve run
again in the backward pass, 5.4 ms a layer, against 1.07 GB of
boundary states alone over four layers held from forward to backward
in a step that already counts 13.4 of the chip's 15.75 GB at once.

`pick_recurrence` (`ops/pallas/gated_delta_rule.py`) answers
"chunked_kernel" for the stateless shape on a TPU with head dims of
whole 128-lane tiles and a row of at least one full chunk; everywhere
else the stateless shape takes `delta_rule_chunked`, behind the same
signature and layout (`CHUNKED_RULES`, `delta_rule_chunked_plain`:
there the by-head form and the norm are jax.numpy's, and
`ops/chunked_delta_rule.py`'s algebra stays as it was, the kernels'
oracle).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..chunked_delta_rule import (delta_rule_chunked_plain, row_rsqrt,
                                  unit_heads)

try:  # lazy-safe: CPU-only envs without pallas never touch the kernel
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pl = pltpu = None

_HIGHEST = jax.lax.Precision.HIGHEST
#: heads a grid program holds at most: a program's blocks (operands,
#: boundary states, gradients; double-buffered) are ~0.5 MB a head in
#: the backward kernel, so 8 stay well inside the 16 MiB a kernel may
#: use without asking (PERF.md section 6, PR 44, has the chip's reading
#: of 1, 4, 8 and 16)
HEADS_PER_PROGRAM = 8


def heads_per_program(num_heads: int) -> int:
    """The most heads, up to `HEADS_PER_PROGRAM`, that divide the
    row's heads (the walk's grid programs)."""
    hb = min(num_heads, HEADS_PER_PROGRAM)
    while num_heads % hb:
        hb -= 1
    return hb


def _products(exact: bool):
    """(a b, a b^T, a^T b) on 2-D tiles, float32 out."""
    def dot(dims):
        return functools.partial(
            jax.lax.dot_general, dimension_numbers=(dims, ((), ())),
            precision=_HIGHEST if exact else None,
            preferred_element_type=jnp.float32)

    return dot(((1,), (0,))), dot(((1,), (1,))), dot(((0,), (0,)))


def _fwd_kernel(s_ref, x_ref, qg_ref, bm_ref, kt_ref, sh_ref,
                so_ref, o_ref, st_ref, *, heads: int, exact: bool):
    """One grid program = (row, head block, chunk): `heads` states
    through one chunk.  `so_ref` (the final states' block, the same for
    every chunk of a row) is where they live in between."""
    nn, nt, tn = _products(exact)
    C = x_ref.shape[-2]
    dt = qg_ref.dtype
    dv = o_ref.shape[-1] // heads

    @pl.when(pl.program_id(2) == 0)
    def _start():
        so_ref[...] = s_ref[...]

    for j in range(heads):
        St = so_ref[0, j]                           # [dv, dk] float32
        st_ref[0, 0, j] = St
        Sb = St.astype(dt)
        x = x_ref[0, 0, j]                          # [u | w] float32
        both = nt(jnp.concatenate([x[:, dv:].astype(dt), qg_ref[0, 0, j]], 0),
                  Sb)
        d = x[:, :dv] - both[:C]                    # [C, dv]
        db = d.astype(dt)
        o_ref[0, :, j * dv:(j + 1) * dv] = both[C:] + nn(bm_ref[0, 0, j], db)
        so_ref[0, j] = St * sh_ref[0, 0, j] + tn(db, kt_ref[0, 0, j])


def _bwd_kernel(ds_ref, x_ref, qg_ref, bm_ref, kt_ref, sh_ref, st_ref,
                do_ref, dsi_ref, dx_ref, dqg_ref, dbm_ref, dkt_ref,
                dsh_ref, *, heads: int, exact: bool):
    """The same grid with the chunks in reverse (the index maps turn
    them): `dsi_ref` holds the heads' dS between chunks and is the
    gradient of the starting state after the row's first."""
    nn, nt, tn = _products(exact)
    C = x_ref.shape[-2]
    dt = qg_ref.dtype
    dv = do_ref.shape[-1] // heads

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dsi_ref[...] = ds_ref[...]

    for j in range(heads):
        St = st_ref[0, 0, j]                        # the chunk's S_0
        Sb = St.astype(dt)
        dS = dsi_ref[0, j]                          # dS' [dv, dk] float32
        dSb = dS.astype(dt)
        x = x_ref[0, 0, j]
        w, qg, kt, bm = (x[:, dv:].astype(dt), qg_ref[0, 0, j],
                         kt_ref[0, 0, j], bm_ref[0, 0, j])
        db = (x[:, :dv] - nt(w, Sb)).astype(dt)
        dob = do_ref[0, :, j * dv:(j + 1) * dv].astype(dt)  # [C, dv]
        dd = tn(bm, dob) + nt(kt, dSb)
        ddb = dd.astype(dt)
        both = nn(jnp.concatenate([ddb, dob], 0), Sb)       # [2C, dk]
        dx_ref[0, 0, j] = jnp.concatenate([dd, -both[:C]], 1)
        dqg_ref[0, 0, j] = both[C:].astype(dqg_ref.dtype)
        dbm_ref[0, 0, j] = nt(dob, db).astype(dbm_ref.dtype)
        dkt_ref[0, 0, j] = nn(db, dSb).astype(dkt_ref.dtype)
        dsh_ref[0, 0, j] = jnp.sum(St * dS, axis=0, keepdims=True)
        dsi_ref[0, j] = dS * sh_ref[0, 0, j] + tn(
            jnp.concatenate([dob, -ddb], 0), jnp.concatenate([qg, w], 0))


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _tril(C):
    """[C, C] float32, ones on and under the diagonal: `tril @ g` is
    the running sum of g's rows, `tril^T @ dG` its transpose."""
    return (_iota((C, C), 0) >= _iota((C, C), 1)).astype(jnp.float32)


def _pairs_tile(q, k, G, sub: int):
    """One head's chunk, float32: q, k and the decays' running sums G
    [C, dk] -> (A strictly lower, B lower [C, C], q exp(G), k exp(G),
    k exp(G_C - G) [C, dk], exp(G_C) [1, dk]):
    `ops/chunked_delta_rule.py`'s `_chunk_matrices` and the scaled
    operands on resident tiles.  A sub-chunk's pairwise
    decays are formed a COLUMN at a time (`exp(G_t - G_i)` for the rows
    t >= i of the sub-chunk, masked before the exponential), the blocks
    left of it are one product against the sub-chunk's base."""
    _, nt, _ = _products(True)
    C = q.shape[0]
    row, col = _iota((C, 1), 0), _iota((1, C), 1)
    t_loc = _iota((sub, 1), 0)
    last = G[C - 1:C]
    a_rows, b_rows = [], []
    for s in range(C // sub):
        lo, hi = s * sub, (s + 1) * sub
        ks, qs, Gs = k[lo:hi], q[lo:hi], G[lo:hi]
        a = b = jnp.zeros((sub, C), jnp.float32)
        if s:
            base = G[lo - 1:lo]  # the sum at the end of sub-chunk s - 1
            r = jnp.exp(Gs - base)  # <= 1
            left = row < lo
            kc = jnp.where(left, k * jnp.exp(jnp.where(left, base - G, 0.0)),
                           0.0)
            off = nt(jnp.concatenate([ks * r, qs * r], 0), kc)  # [2 sub, C]
            a, b = off[:sub], off[sub:]
        for i in range(sub):
            low = t_loc >= i
            KE = ks[i:i + 1] * jnp.where(
                low, jnp.exp(jnp.where(low, Gs - Gs[i:i + 1], 0.0)), 0.0)
            kk = jnp.sum(KE * ks, axis=1, keepdims=True)  # [sub, 1]
            qk = jnp.sum(KE * qs, axis=1, keepdims=True)
            a = jnp.where(col == lo + i, jnp.where(t_loc > i, kk, 0.0), a)
            b = jnp.where(col == lo + i, qk, b)
        a_rows.append(a)
        b_rows.append(b)
    eG = jnp.exp(G)
    return (jnp.concatenate(a_rows, 0), jnp.concatenate(b_rows, 0), q * eG,
            k * eG, k * jnp.exp(last - G), jnp.exp(last))


def _pairs_tile_bwd(q, k, G, sub: int, dA, dB, dqg, dkg, dkt, dsh):
    """The gradient of `_pairs_tile` from its outputs' (float32) to q,
    k, G, everything recomputed from them.  With E_ti = exp(G_t - G_i)
    per channel, X_t = sum_i dA_ti k_i E_ti, Y_t = sum_i dB_ti k_i E_ti
    and Z_i = sum_t (dA_ti k_t + dB_ti q_t) E_ti:
        dq = Y,  dk = X + Z,  dG = k X + q Y - k Z
    plus the scaled operands' terms; the blocks left of a sub-chunk give
    their share of X, Y, Z through two products (the base cancels)."""
    nn, _, tn = _products(True)
    C = q.shape[0]
    row, col = _iota((C, 1), 0), _iota((1, C), 1)
    t_loc = _iota((sub, 1), 0)
    last = G[C - 1:C]
    xs, ys, zs = [], [], []
    z_left = jnp.zeros_like(q)
    for s in range(C // sub):
        lo, hi = s * sub, (s + 1) * sub
        ks, qs, Gs = k[lo:hi], q[lo:hi], G[lo:hi]
        dAs, dBs = dA[lo:hi], dB[lo:hi]
        X = Y = Z = jnp.zeros_like(ks)
        if s:
            base = G[lo - 1:lo]
            r = jnp.exp(Gs - base)
            left = row < lo
            ec = jnp.where(left, jnp.exp(jnp.where(left, base - G, 0.0)), 0.0)
            d_off = jnp.where(col < lo, jnp.concatenate([dAs, dBs], 0), 0.0)
            d_rows = nn(d_off, k * ec)                     # [2 sub, dk]
            X, Y = d_rows[:sub] * r, d_rows[sub:] * r
            z_left = z_left + ec * tn(
                d_off, jnp.concatenate([ks * r, qs * r], 0))
        for i in range(sub):
            low = t_loc >= i
            E = jnp.where(low, jnp.exp(jnp.where(low, Gs - Gs[i:i + 1], 0.0)),
                          0.0)
            da = jnp.where(t_loc > i, dAs[:, lo + i:lo + i + 1], 0.0)
            db = dBs[:, lo + i:lo + i + 1]                 # [sub, 1]
            KE = ks[i:i + 1] * E
            X, Y = X + da * KE, Y + db * KE
            Z = jnp.where(t_loc == i, jnp.sum((da * ks + db * qs) * E, axis=0,
                                              keepdims=True), Z)
        xs.append(X)
        ys.append(Y)
        zs.append(Z)
    X, Y = jnp.concatenate(xs, 0), jnp.concatenate(ys, 0)
    Z = jnp.concatenate(zs, 0) + z_left
    eG, et = jnp.exp(G), jnp.exp(last - G)
    dkt_kt = dkt * k * et
    dG = (k * (X - Z) + q * Y + (dqg * q + dkg * k) * eG - dkt_kt
          + jnp.where(row == C - 1, jnp.sum(dkt_kt, axis=0, keepdims=True)
                      + dsh * jnp.exp(last), 0.0))
    return Y + dqg * eG, X + Z + dkg * eG + dkt * et, dG


def _l2norm_bwd(x, dy):
    """The gradient of `l2norm` at x: with r the row's rsqrt and
    y = r x, dx = r (dy - y (y . dy)) (exact with the eps)."""
    r = row_rsqrt(x)
    y = x * r
    return r * (dy - y * jnp.sum(y * dy, axis=-1, keepdims=True))


def _operands_tile(q, k, v, g, beta, sub: int):
    """One head's chunk, float32: the convs' q~, k~ and g [C, dk], v
    [C, dv], beta [C, 1] -> (A, the solve's system I + Diag(beta) A
    [C, C], its right side [beta V | beta exp(G) K] [C, dv + dk], B,
    q exp(G), k exp(G_C - G), exp(G_C)) of q, k = `unit_heads(q~, k~)`,
    normalised here, on the tile."""
    C = q.shape[0]
    q, k = unit_heads(q, k)
    A, B, qg, kg, kt, shrink = _pairs_tile(
        q, k, _products(True)[0](_tril(C), g), sub)
    eye = (_iota((C, C), 0) == _iota((C, C), 1)).astype(jnp.float32)
    return (A, eye + beta * A, jnp.concatenate([beta * v, beta * kg], 1), B,
            qg, kt, shrink)


def _operands_tile_bwd(q, k, v, g, beta, sub: int, A, d_sys, d_rhs, dB, dqg,
                       dkt, dsh):
    """The gradient of `_operands_tile` (but for `A`, a residual) to
    the q~, k~ it was given, v, g and beta."""
    nn, _, tn = _products(True)
    raw = q, k
    q, k = unit_heads(q, k)
    dv = v.shape[1]
    d_v, d_kg = d_rhs[:, :dv], d_rhs[:, dv:]
    tril = _tril(q.shape[0])
    G = nn(tril, g)
    d_beta = (jnp.sum(d_sys * A, axis=1, keepdims=True)
              + jnp.sum(d_v * v, axis=1, keepdims=True)
              + jnp.sum(d_kg * k * jnp.exp(G), axis=1, keepdims=True))
    dq, dk, dG = _pairs_tile_bwd(q, k, G, sub, beta * d_sys, dB, dqg,
                                 beta * d_kg, dkt, dsh)
    return (_l2norm_bwd(raw[0], dq * q.shape[-1] ** -0.5),
            _l2norm_bwd(raw[1], dk), beta * d_v, tn(tril, dG), d_beta)


def _specs(kinds: str, shapes, heads: int, grid, chunk: int, reverse: bool):
    """A grid program's block of each array, by its kind: "c" `heads`
    heads of one chunk of `[n, b, h, ...]`, "r" of a row `[b, h, ...]`,
    "p" the chunk's positions of `heads` heads where the op left them,
    flat: `heads` column blocks of `[b, s, h d]`, a head d lanes wide;
    the chunk index runs backwards under `reverse`."""
    _, h, n = grid

    def at(t):
        return n - 1 - t if reverse else t

    def spec(kind, sp):
        if kind == "c":
            return pl.BlockSpec((1, 1, heads) + sp[3:],
                                lambda i, j, t: (at(t), i, j, 0, 0))
        if kind == "r":
            return pl.BlockSpec((1, heads) + sp[2:],
                                lambda i, j, t: (i, j, 0, 0))
        return pl.BlockSpec((1, chunk, sp[2] // h * heads),
                            lambda i, j, t: (i, at(t), j))

    return [spec(kind, tuple(sp)) for kind, sp in zip(kinds, shapes)]


def _call(kernel, name, heads, interpret, kinds, args, outs, *, grid, chunk,
          reverse=False, **static):
    """`kernel` over (row, head block, chunk); `grid` = (b, h, n);
    `kinds` names each argument's and then each output's blocks."""
    b, h, n = grid
    specs = _specs(kinds, [a.shape for a in list(args) + list(outs)], heads,
                   grid, chunk, reverse)
    return pl.pallas_call(
        functools.partial(kernel, heads=heads, **static),
        grid=(b, h // heads, n),
        in_specs=specs[:len(args)],
        out_specs=specs[len(args):],
        out_shape=outs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(*args)


def _is_exact(w):
    return jnp.dtype(w.dtype) == jnp.dtype(jnp.float32)


def _walk_fwd(heads, interpret, St, x, qg, bm, kt, shrink):
    n, b, h, C, _ = x.shape
    dv = St.shape[-2]
    f32 = jnp.float32
    St, o, states = _call(
        _fwd_kernel, "delta_rule_chunks_fwd", heads, interpret,
        "rccccc" "rpc", (St, x, qg, bm, kt, shrink),
        [jax.ShapeDtypeStruct(St.shape, f32),
         jax.ShapeDtypeStruct((b, n * C, h * dv), f32),
         jax.ShapeDtypeStruct((n,) + St.shape, f32)],
        grid=(b, h, n), chunk=C, exact=_is_exact(qg))
    return (St, o), (x, qg, bm, kt, shrink, states)


def _walk_bwd(heads, interpret, res, cts):
    x, qg, bm, kt, shrink, states = res
    dSt, do = cts
    n, b, h, C, _ = x.shape
    like = jax.ShapeDtypeStruct
    return tuple(_call(
        _bwd_kernel, "delta_rule_chunks_bwd", heads, interpret,
        "rcccccc" "p" "rccccc", (dSt, x, qg, bm, kt, shrink, states, do),
        [like(dSt.shape, jnp.float32)]
        + [like(t.shape, t.dtype) for t in (x, qg, bm, kt, shrink)],
        grid=(b, h, n), chunk=C, reverse=True, exact=_is_exact(qg)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _walk(heads, interpret, St, x, qg, bm, kt, shrink):
    """(St [b, h, dv, dk] float32, the solve's [u | w] float32 and the
    other operands) -> (the final states, o [b, n C, h dv] float32: flat
    as the operands came, head j's output column block j)."""
    return _walk_fwd(heads, interpret, St, x, qg, bm, kt, shrink)[0]


_walk.defvjp(_walk_fwd, _walk_bwd)


def _head_loop(heads: int, body):
    """`body(j)` for each head of the block, traced ONCE (the tile
    functions unroll a sub-chunk and a column: a Python loop over the
    heads traced them `heads` times, 13 s of a 30 s warm first step)
    and unrolled by the lowering, so that the heads' chains overlap
    (left as a loop: the forward kernel 2.6 ms for 1.9)."""
    def step(j, carry):
        body(j)
        return carry

    jax.lax.fori_loop(0, heads, step, 0, unroll=True)


def _head_columns(ref, j, heads: int):
    """Where head j's `[C, d]` lies in a flat `(1, C, heads d)` block:
    column block j, whole lane tiles (d is a multiple of 128 on the
    chip), so reading or writing it moves no data across lanes."""
    d = ref.shape[-1] // heads
    return 0, slice(None), pl.ds(pl.multiple_of(j * d, d), d)


def _operands_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, *out_refs,
                         heads: int, sub: int):
    """One grid program = (row, head block, chunk): `_operands_tile` of
    each head, q, k, v, g read where the op left them, flat."""
    f32 = jnp.float32

    def head(j):
        outs = _operands_tile(
            *(ref[_head_columns(ref, j, heads)].astype(f32)
              for ref in (q_ref, k_ref, v_ref, g_ref)), beta_ref[0, 0, j], sub)
        for ref, out in zip(out_refs, outs):
            ref[0, 0, j] = out.astype(ref.dtype)

    _head_loop(heads, head)


def _operands_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, *refs,
                         heads: int, sub: int):
    """The same grid: the gradients leave flat too, where the convs'
    and the decay projection's backward read them."""
    f32 = jnp.float32
    places = (q_ref, k_ref, v_ref, g_ref)
    cts, grads = refs[:-5], refs[-5:]  # A and the outputs' | dq .. dbeta

    def head(j):
        out = _operands_tile_bwd(
            *(ref[_head_columns(ref, j, heads)].astype(f32)
              for ref in places),
            beta_ref[0, 0, j], sub,
            *(ref[0, 0, j].astype(f32) for ref in cts))
        for ref, grad in zip(grads[:4], out):
            ref[_head_columns(ref, j, heads)] = grad.astype(ref.dtype)
        grads[4][0, 0, j] = out[4]

    _head_loop(heads, head)


def _operands_fwd(interpret, chunk, sub, dt, q, k, v, g, beta):
    (b, s, _), h = q.shape, beta.shape[2]
    n, dk, dv = s // chunk, q.shape[2] // h, v.shape[2] // h
    f32 = jnp.float32

    def out(last, dtype):
        return jax.ShapeDtypeStruct((n, b, h) + last, dtype)

    A, *outs = _call(
        _operands_fwd_kernel, "delta_rule_operands_fwd", heads_per_program(h),
        interpret, "ppppc" "ccccccc", (q, k, v, g, beta),
        [out((chunk, chunk), f32), out((chunk, chunk), f32),
         out((chunk, dv + dk), f32), out((chunk, chunk), dt),
         out((chunk, dk), dt), out((chunk, dk), dt), out((1, dk), f32)],
        grid=(b, h, n), chunk=chunk, sub=sub)
    return tuple(outs), (q, k, v, g, beta, A)


def _operands_bwd(interpret, chunk, sub, dt, res, cts):
    (b, s, _), h = res[0].shape, res[4].shape[2]
    return tuple(_call(
        _operands_bwd_kernel, "delta_rule_operands_bwd", heads_per_program(h),
        interpret, "ppppc" "ccccccc" "ppppc", tuple(res) + tuple(cts),
        [jax.ShapeDtypeStruct(t.shape, t.dtype) for t in res[:5]],
        grid=(b, h, s // chunk), chunk=chunk, sub=sub))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _operands(interpret, chunk, sub, dt, q, k, v, g, beta):
    """The convs' q~, k~ and g [b, s, h dk], v [b, s, h dv] (s whole
    chunks), beta [n, b, h, C, 1] -> (the solve's system [n, b, h, C, C]
    and right side [n, b, h, C, dv + dk], float32; B [n, b, h, C, C],
    q exp(G) and k exp(G_C - G) [n, b, h, C, dk] in `dt`, the walk's
    operand dtype; exp(G_C) [n, b, h, 1, dk] float32), q and k
    normalised on the tile; the residuals and gradients are q~'s and
    k~'s."""
    return _operands_fwd(interpret, chunk, sub, dt, q, k, v, g, beta)[0]


_operands.defvjp(_operands_fwd, _operands_bwd)


def delta_rule_chunked_kernel(S, q, k, v, g, beta, chunk: int, sub: int,
                              operand_dtype=jnp.float32, *,
                              heads_block: Optional[int] = None,
                              interpret: Optional[bool] = None):
    """`CHUNKED_RULES`' rule as the kernels above around XLA's solve:
    same arguments as `delta_rule_chunked_plain` (q~, k~, v, g flat,
    `[b, s, h d]`; the l2norm of q~ and k~ the rule's), same result to
    rounding, differentiable in S, q~, k~, v, g and beta.  `interpret`
    defaults from the backend (compiled by Mosaic on a TPU, interpreted
    on a CPU: the tests' vehicle); `heads_block` (a probe's:
    `scripts/kda_core_probe.py`) overrides the walk's
    `heads_per_program`."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = not on_tpu
    elif interpret and on_tpu:
        raise ValueError(
            "delta_rule_chunked_kernel(interpret=True) on the TPU backend: "
            "the kernels must run compiled there")
    if chunk % sub:
        raise ValueError(f"chunk {chunk} is no multiple of sub-chunk {sub}")
    return _rule(S, q, k, v, g, beta, chunk=chunk, sub=sub,
                 operand_dtype=jnp.dtype(operand_dtype),
                 heads=heads_block or heads_per_program(S.shape[1]),
                 interpret=interpret)


# jitted, so a step of N layers lowers each kernel body once (as the
# flash kernels are, flash_attention.py)
@functools.partial(jax.jit, static_argnames=(
    "chunk", "sub", "operand_dtype", "heads", "interpret"))
def _rule(S, q, k, v, g, beta, *, chunk, sub, operand_dtype, heads,
          interpret):
    b, s = q.shape[:2]
    h = S.shape[1]
    f32 = jnp.float32
    n = -(-s // chunk)

    def whole(t):  # positions that leave the state as it was
        return jnp.pad(t, ((0, 0), (0, n * chunk - s), (0, 0)))

    beta = jnp.moveaxis(whole(beta.astype(f32)).reshape(b, n, chunk, h),
                        (1, 3), (0, 2))[..., None]  # [n, b, h, chunk, 1]
    system, rhs, bm, qg, kt, shrink = _operands(
        interpret, chunk, sub, operand_dtype,
        *(whole(t) for t in (q, k, v, g)), beta)
    solved = jax.scipy.linalg.solve_triangular(
        system, rhs, lower=True, unit_diagonal=True)
    St, o = _walk(heads, interpret, jnp.swapaxes(S.astype(f32), -1, -2),
                  solved, qg, bm, kt, shrink)
    return jnp.swapaxes(St, -1, -2), o[:, :s]


#: `pick_recurrence`'s answers for the stateless shape and what runs
#: each: one signature, (S, q~, k~, v, g, beta, chunk, sub,
#: operand_dtype), and one layout: q~, k~, v, g flat, `[b, s, h d]`
CHUNKED_RULES = {"chunked": delta_rule_chunked_plain,
                 "chunked_kernel": delta_rule_chunked_kernel}
