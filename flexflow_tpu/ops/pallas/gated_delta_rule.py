"""The gated delta rule's per-slot recurrence as ONE Pallas call a
layer (TPU): a row's state crosses HBM once a dispatch.

    for each position t of the step, per row and value head:
        S = exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S = S + k_t d^T
        o_t = S^T q_t                   (`ops/gated_delta_net.py
                                         delta_rule_step`, float32)

The plain recurrence is a `lax.scan` of that step over `[slots, h, dk,
dv]`: XLA runs `S^T k` and `S^T q` as float32 multiply-reduces and the
decay and the outer product in other fusions, three to four passes
over EVERY slot's state a position.  Here a grid program holds a block
of one row's `[dk, dv]` tiles in VMEM, walks the step's positions with
them resident (same operations, same order, float32 on the vector
unit) and writes them back once, onto the input's own buffer
(`input_output_aliases`; the step programs donate their state).

Rows that do not advance (`count == 0`: idle slots, the riders of a
prefill dispatch) are neither read nor written, so their state is the
input's bytes by construction.  The grid walks a COMPACTED list of the
live rows (scalar prefetch, like `paged_attention`'s block table): the
entries past the last live row repeat its last block, which Pallas's
pipeline neither fetches nor writes again, and their body is skipped.
Positions past a live row's count arrive with `beta = 0, g = 0` as on
the plain path and leave its state as it was (`1 S + k 0`).

Layout (what Mosaic accepts, CHANGES.md PR 35).  With `S` `[dk, dv]`,
dk on sublanes and dv on lanes, `S^T k` is a reduction over sublanes
and gives a lane vector, which is how `v`, `d` and `o` live; `k` and
`q` have to be COLUMNS (dk on sublanes, broadcast along lanes).  So the
wrapper hands them in transposed, `[rows, head blocks, dk, heads of a
block x positions]` (a plain XLA transpose of a tensor 1/16th of the
state), and the body takes column `j s + t` by a static lane slice; `v`
and `o` are `[.., heads of a block x positions, dv]`, rows of the same
index.  The per-position scalars `exp(g)` and `beta` ride with `v` as
rows of their own, each repeated along the 128 lanes (a row times a
tile broadcasts along sublanes), so the body has vector operands only.
Every index inside the body is static: heads of a block and positions
are unrolled, which is why the step length is bounded
(`MAX_STEP_TOKENS`).  A grid program's blocks stay inside the 16 MiB of
VMEM a kernel may use without asking (`_BLOCK_STATE_BYTES`): asking for
32 MiB (`vmem_limit_bytes`) ran alone and in a program of six ops, and
HUNG the device inside the served step programs, where XLA keeps
thousands of small buffers in VMEM around the call.

`pick_recurrence` decides between this kernel and the plain recurrence
from what can be observed (backend, whether the op carries per-slot
state, head dims, step length), in the manner of
`flash_attention.pick_tiling`.  There is no backward pass here: the
stateless shape (what a trainer differentiates) takes the chunked rule,
`ops/chunked_delta_rule.py` with its scan over chunks differentiated by
jax, or, where `pick_recurrence` finds a TPU, 128-lane head dims and a
row of at least one full chunk, `ops/pallas/chunked_delta_rule.py`,
the same rule as kernels of its own, forward and backward.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..chunked_delta_rule import CHUNK_TOKENS

try:  # lazy-safe: CPU-only envs without pallas never touch the kernel
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _HAVE_PALLAS = True
except Exception:  # pragma: no cover
    _HAVE_PALLAS = False

#: longest step (tokens a row) the kernel takes: heads x positions are
#: unrolled in the body and a block's k / q columns share 128 lanes
MAX_STEP_TOKENS = 16
#: columns (heads of a block x positions) a grid program unrolls at
#: most: one 128-lane tile of transposed k / q
_BLOCK_COLUMNS = 128
#: float32 state a grid program holds at most: 2 MB (32 heads of 128 x
#: 128), in and out and double-buffered 8 MB, inside the 16 MiB a kernel
#: may use without asking
_BLOCK_STATE_BYTES = 2 << 20


def pick_recurrence(backend: str, slot_state: bool, head_k_dim: int,
                    head_v_dim: int, step_tokens: int) -> str:
    """Which recurrence a delta-rule op's step takes.  A pure function
    of its arguments.  The stateless shape (what a trainer
    differentiates) runs a chunk of positions at a time: "chunked"
    (`ops/chunked_delta_rule.py`, plain jax.numpy, every backend) or
    "chunked_kernel" (`ops/pallas/chunked_delta_rule.py`: the same rule
    as Pallas kernels, forward and backward) on a TPU, with
    head dims of whole 128-lane tiles and a row of at least one full
    chunk.  With per-slot state: "kernel" (this file) on a TPU, with
    such head dims and a step short enough to unroll, else "plain" (the
    jax.numpy scan a position)."""
    tiles = (backend == "tpu" and _HAVE_PALLAS
             and head_k_dim % 128 == 0 and head_v_dim % 128 == 0)
    if not slot_state:
        if tiles and step_tokens >= CHUNK_TOKENS:
            return "chunked_kernel"
        return "chunked"
    if tiles and 1 <= step_tokens <= MAX_STEP_TOKENS:
        return "kernel"
    return "plain"


def heads_per_block(num_heads: int, step_tokens: int,
                    tile_bytes: int = 128 * 128 * 4) -> int:
    """Value heads a grid program holds: the most that divide the heads,
    keep heads x positions inside one 128-lane tile and their `[dk, dv]`
    tiles inside `_BLOCK_STATE_BYTES`."""
    hb = max(1, min(num_heads, _BLOCK_COLUMNS // step_tokens,
                    _BLOCK_STATE_BYTES // tile_bytes))
    while num_heads % hb:
        hb -= 1
    return hb


def live_rows(count):
    """`count` [b] -> (rows [b], n [1]), int32: the rows with a count
    above 0 in order, padded by repeating the last of them (the last
    row when none is live), and how many there are."""
    b = count.shape[0]
    seen = jnp.cumsum((count > 0).astype(jnp.int32))
    n = seen[-1]
    at = jnp.minimum(jnp.arange(b, dtype=jnp.int32), jnp.maximum(n - 1, 0))
    # the (j + 1)-th live row is preceded by the rows that have seen at
    # most j live ones (no sort: a [b, b] comparison)
    rows = jnp.sum(seen[None, :] <= at[:, None], axis=1, dtype=jnp.int32)
    return jnp.minimum(rows, b - 1), n.reshape(1)


def _kernel(rows_ref, n_ref, s_ref, kt_ref, qt_ref, vgb_ref, so_ref, o_ref,
            *, heads: int, steps: int):
    """One grid program = (entry i of the live-row list, head block):
    `heads` tiles of that row's state through `steps` positions.
    `vgb_ref` stacks v, exp(g) and beta, a row a (head, position)."""
    i, hb = pl.program_id(0), pl.program_id(1)
    n = n_ref[0]
    cols = heads * steps

    @pl.when(i < n)
    def _advance():
        for j in range(heads):
            S = s_ref[0, j]                        # [dk, dv]
            for t in range(steps):
                c = j * steps + t
                kc = kt_ref[0, 0, :, c:c + 1]      # [dk, 1]
                qc = qt_ref[0, 0, :, c:c + 1]
                v = vgb_ref[0, 0, c:c + 1, :]      # [1, dv]
                decay = vgb_ref[0, 0, cols + c:cols + c + 1, :]
                beta = vgb_ref[0, 0, 2 * cols + c:2 * cols + c + 1, :]
                S = S * decay
                u = jnp.sum(S * kc, axis=0, keepdims=True)   # [1, dv]
                d = beta * (v - u)
                S = S + kc * d
                o_ref[0, 0, c:c + 1, :] = jnp.sum(S * qc, axis=0,
                                                  keepdims=True)
            so_ref[0, j] = S

    # no row is live: every entry maps the same block, which the
    # pipeline writes back at the end whatever the body did
    @pl.when((n == 0) & (i == 0) & (hb == 0))
    def _keep():
        so_ref[...] = s_ref[...]


def gated_delta_rule(S, q, k, v, g, beta, count, *,
                     interpret: Optional[bool] = None,
                     heads_block: Optional[int] = None):
    """The recurrence over a step, rows that do not advance skipped.

    S:       [b, h, dk, dv] float32, each row's state coming in
    q, k:    [b, s, h, dk]  float32 (q scaled, both normalised)
    v:       [b, s, h, dv]  float32
    g, beta: [b, s, h]      float32, 0 at positions past a row's count
    count:   [b] int32      tokens of the step a row really has
    ->       (S [b, h, dk, dv], o [b, s, h, dv])

    Row i of the returned S is the input's where `count[i] == 0`, and
    its `o` is 0.  `interpret` defaults from the backend, as in
    `paged_attention`: compiled by Mosaic on a TPU, interpreted on a
    CPU (the tests' vehicle), never interpreted on a TPU.
    `heads_block` (a probe's: `scripts/gdn_kernel_probe.py`) overrides
    `heads_per_block`."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = not on_tpu
    elif interpret and on_tpu:
        raise ValueError(
            "gated_delta_rule(interpret=True) on the TPU backend: the "
            "kernel must run compiled there")
    heads = heads_block or heads_per_block(
        S.shape[1], q.shape[1], S.shape[2] * S.shape[3] * 4)
    return _rule(S, q, k, v, g, beta, count, heads=heads,
                 interpret=interpret)


# jitted, so a step of N layers lowers ONE kernel body (as the one-tile
# attention kernels are, flash_attention.py)
@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _rule(S, q, k, v, g, beta, count, *, heads: int, interpret: bool):
    b, h, dk, dv = S.shape
    s, hb = q.shape[1], heads
    nb, cols = h // hb, hb * s
    f32 = jnp.float32
    count = count.reshape(b).astype(jnp.int32)
    rows, n = live_rows(count)

    def columns(x):   # [b, s, h, d] -> [b, nb, d, hb * s]
        return x.astype(f32).reshape(b, s, nb, hb, -1).transpose(
            0, 2, 4, 3, 1).reshape(b, nb, -1, cols)

    def lanes(x):     # [b, s, h, d or none] -> [b, nb, hb * s, dv]
        x = x.astype(f32).reshape(b, s, nb, hb, -1).transpose(0, 2, 3, 1, 4)
        return jnp.broadcast_to(x, (b, nb, hb, s, dv)).reshape(
            b, nb, cols, dv)

    def block(i, j, rows, n):
        # entries past the last live row stay on its last block
        return rows[i], jnp.where(i < n[0], j, nb - 1), 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nb),
        in_specs=[pl.BlockSpec((1, hb, dk, dv), block),
                  pl.BlockSpec((1, 1, dk, cols), block),
                  pl.BlockSpec((1, 1, dk, cols), block),
                  pl.BlockSpec((1, 1, 3 * cols, dv), block)],
        out_specs=[pl.BlockSpec((1, hb, dk, dv), block),
                   pl.BlockSpec((1, 1, cols, dv), block)],
    )
    vgb = jnp.concatenate([lanes(v), lanes(jnp.exp(g)), lanes(beta)], axis=2)
    S, o = pl.pallas_call(
        functools.partial(_kernel, heads=hb, steps=s),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, dk, dv), f32),
                   jax.ShapeDtypeStruct((b, nb, cols, dv), f32)],
        # operand 2 (after the two prefetched tables) is S: rows the
        # grid never visits keep the input's bytes
        input_output_aliases={2: 0},
        interpret=interpret,
        name="gated_delta_rule",
    )(rows, n, S.astype(f32), columns(k), columns(q), vgb)
    o = o.reshape(b, nb, hb, s, dv).transpose(0, 3, 1, 2, 4).reshape(
        b, s, h, dv)
    # a skipped row's blocks of `o` were never written
    return S, jnp.where((count > 0)[:, None, None, None], o, 0.0)
