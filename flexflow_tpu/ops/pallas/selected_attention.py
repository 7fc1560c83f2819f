"""Latent attention over PICKED keys, read in place (Pallas TPU): the
selected read of `ops/mla.py` ("Selected keys") as a walk over a row's
live pages of the paged latent pool, with the picks as a mask inside
the fold.

The two XLA formulations of that read pay for the wrong thing under a
table a few times `index_topk` wide: the gather moves `s x index_topk`
token rows a row of the batch one by one (19 ns a 1.3 kB row), the
masked view gathers, scores and masks the TABLE'S width for every query
and keeps `[b, h, s, n]` float32 scores in HBM (PERF.md, PR 57).  What a
chunk of s queries needs is the row's LIVE keys, once: with s queries a
row the union of their picks is nearly every live key, so the kernel
walks the live pages and lets each query's picks decide what its
softmax sees.

Grid `(b,)`, one program a row, `block_table` and `seq_lens`
scalar-prefetched, the pool `[num_blocks, page, width]` passed whole
with `memory_space=pl.ANY` (PR 56's walk, `paged_attention.py
_head_major_kernel`, whose copy discipline this is).  A page `[page,
width]` is one contiguous slab (`MLAttention.pool_width` pads a token's
row to whole 128-lane tiles, and page = 16 is bf16's sublane tile); a
tile of N pages (`pages_per_tile`) is copied by hand, each page to where
its keys fall in `[2, N * page, width]`, tile j + 1 in flight while tile
j is folded, and the trip count is `ceil(live / N)` with `live` the
pages positions `0 .. seq_len + s - 1` touch (the step's own tokens are
written before the read).  A parked row walks its one scratch page; the
spare columns of a row's last tile repeat its last live page, so every
byte a product reads was written by a copy.

A fold takes the tile's keys `[N * page, width]` against the row's
latent queries `[h * s, width]` (`[q_nope W_kvb_k^T | q_rope | 0]`, made
outside, head-major: row `g * s + t` is head g's query of chunk token
t), `rows_per_fold` query rows at a time so that a block's float32
scores stay small: scores in float32 on the MXU, times the softmax
scale, the tile's slice of the mask `[s, N * page]` (one byte a pair,
the whole row's mask resident in VMEM; the same slice serves every
head: a block of query rows is whole heads), the float32 online softmax,
and `acc += p . tile[:, :rank]` with p rounded to the pool's precision
as the XLA formulations round it.  The mask is the whole rule: picks
are causal, hold `-1` where a query has fewer than `index_topk` keys,
and never name a position past the row's length, so the kernel needs no
position arithmetic of its own.  A masked pair's probability is exactly
0 (its score is -1e30 under a running maximum that starts at -1e29),
and a key NO query of the chunk picks has its value row zeroed before
the product, so what stands in an unpicked row or past a row's length
(NaN included) reaches no context.

On the CPU the kernel runs under `interpret=True` (tests/
test_selected_walk.py, against both XLA formulations); on the TPU Mosaic
compiles it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .paged_attention import (_HAVE_PALLAS, _NEG_INF, _live_block_count,
                              _mxu)

if _HAVE_PALLAS:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

#: where a row's running maximum starts: above a masked score (-1e30)
#: by so much that `exp(masked - max)` is 0 while no key has been seen
_M_FLOOR = -1e29
#: keys a tile of the walk holds: whole 128-lane tiles of the mask
#: (scripts/selected_read_probe.py at cell 12's shapes and mix of lengths,
#: 16 queries a row: 2.54 / 2.24 / 2.28 ms a launch at 256 / 512 / 1,024;
#: a parked row's one tile 0.30 / 0.42 / 0.69: PERF.md, PR 58)
TILE_KEYS = 512
#: query rows a fold scores at once: `[512, N * page]` float32 scores
#: are 1 MB at 512 keys (the same probe: 2.65 / 2.35 / 2.24 ms at 128 /
#: 256 / 512 rows: the MXU holds a tile's keys longer)
ROWS_PER_FOLD = 512
#: what the kernel may use of VMEM: the row's queries and output (double
#: buffered by the pipeline), the accumulators, the row's mask, the
#: tiles and a fold's scores are ~14 MB at 64 heads x 16 queries
_VMEM_LIMIT_BYTES = 48 << 20


def pages_per_tile(page: int) -> int:
    """Pages a tile of the walk holds, from the launch's shapes alone:
    `TILE_KEYS` keys (32 pages of 16)."""
    return max(1, TILE_KEYS // page)


def _fold_selected(q_ref, tile, keep, m_ref, l_ref, acc_ref, *,
                   scale: float, chunk: int, rank: int, fold_rows: int):
    """Fold one tile's keys `tile [cols, width]` into the row's online
    softmax under `keep [chunk, cols]` (non-zero where chunk token t
    picked the key).  q_ref `[1, h * chunk, width]`, head-major."""
    rows, cols = q_ref.shape[1], tile.shape[0]
    keep = keep.astype(jnp.float32) > 0.0  # [chunk, cols]
    # the value row of a key no query picks is zeroed: its probability
    # is 0 for every query, and 0 x NaN would still be NaN.  The keys lie
    # along the mask's lanes and along the tile's sublanes: the count of
    # a key's picks, transposed by a product with ones
    picked = _mxu(keep.astype(tile.dtype),
                  jnp.ones((chunk, 128), tile.dtype),
                  ((0,), (0,)))[:, :1] > 0.0  # [cols, 1]
    values = jnp.where(picked, tile[:, :rank], jnp.zeros_like(tile[:, :rank]))
    # a block of query rows is whole heads, token-minor: one mask
    if chunk == 1:
        keep_rows = keep
    else:  # (heads, chunk, cols) merges for free when chunk % 8 == 0
        keep_rows = jnp.broadcast_to(
            keep[None], (fold_rows // chunk, chunk, cols)).reshape(
                fold_rows, cols)
    for r in range(0, rows, fold_rows):
        at = slice(r, r + fold_rows)
        s = _mxu(q_ref[0, at], tile, ((1,), (1,))) * scale
        s = jnp.where(keep_rows, s, _NEG_INF)
        m_prev = m_ref[at]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        pr = jnp.exp(s - m_new)  # a masked pair: exp(<= -9e29) = 0
        corr = jnp.exp(m_prev - m_new)
        l_ref[at] = l_ref[at] * corr + jnp.sum(pr, axis=1, keepdims=True)
        acc_ref[at] = acc_ref[at] * corr + _mxu(
            pr.astype(tile.dtype), values, ((1,), (0,)))
        m_ref[at] = m_new


def _walk_kernel(btab_ref, slen_ref, q_ref, keep_ref, pool_hbm, o_ref,
                 buf, sems, m_ref, l_ref, acc_ref, *, page: int,
                 scale: float, table_width: int, chunk: int, pages: int,
                 rank: int, fold_rows: int):
    """One grid program = one row: walk the row's live pages `pages` at
    a time.  The pool stays in HBM; a tile's pages are copied by hand
    into `buf [2, pages * page, width]`, tile j + 1's copies in flight
    while tile j is folded (`_fold_selected`)."""
    i = pl.program_id(0)
    live = _live_block_count(slen_ref[i], chunk, page, table_width)
    tiles = pl.cdiv(live, pages)

    def copies(tile, slot):
        # a column past the row's live pages repeats its last live page
        # (no query picks a key there): every byte a product reads was
        # written by a copy
        for p in range(pages):
            block = btab_ref[i, jnp.minimum(tile * pages + p, live - 1)]
            yield pltpu.make_async_copy(
                pool_hbm.at[block], buf.at[slot, pl.ds(p * page, page)],
                sems.at[slot])

    m_ref[...] = jnp.full_like(m_ref, _M_FLOOR)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    for copy in copies(0, 0):
        copy.start()

    def tile_step(j, _):
        slot = j % 2

        @pl.when(j + 1 < tiles)
        def _ahead():
            for copy in copies(j + 1, 1 - slot):
                copy.start()

        for copy in copies(j, slot):
            copy.wait()
        _fold_selected(q_ref, buf[slot], keep_ref[0, j], m_ref, l_ref,
                       acc_ref, scale=scale, chunk=chunk, rank=rank,
                       fold_rows=fold_rows)

    jax.lax.fori_loop(0, tiles, tile_step, None)
    l = l_ref[...]
    o_ref[0] = (acc_ref[...] / jnp.where(l > 0.0, l, 1.0)).astype(o_ref.dtype)


def walk_fits(chunk: int, page: int, width: int) -> bool:
    """Whether Mosaic takes the walk at these shapes: a page is whole
    sublane tiles of whole lane tiles (bf16 `[16, 640]`), and the mask
    of a block of query rows is one broadcast of the chunk's."""
    return (width % 128 == 0 and page % 16 == 0
            and (chunk == 1
                 or chunk % 8 == 0 and ROWS_PER_FOLD % chunk == 0))


def selected_latent_attention(q_lat, pool, block_table, seq_lens, keep,
                              scale: float, rank: int, *,
                              interpret: Optional[bool] = None,
                              pages_per_step: Optional[int] = None,
                              rows_per_fold: Optional[int] = None):
    """Latent attention of a step's queries over their picked keys,
    read out of the paged pool by a walk over each row's live pages.

    q_lat:       [b, s, h, width]  the queries in the latent space,
                 `[q_nope W_kvb_k^T | q_rope | 0]`
    pool:        [num_blocks, page, width]  a token's row: the latent,
                 its rope key, zeros up to `width`
    block_table: [b, table_width] int32;  seq_lens: [b] int32, a row's
                 incoming position (its s tokens already written)
    keep:        [b, s, table_width * page] bool: query (i, j) attends
                 key position n (`MLAttention._picks_mask`)
    ->           [b, s, h, rank]  sum_n softmax(q . key * scale)_n x
                 key[:rank], q_lat's dtype

    `pages_per_step` and `rows_per_fold` (the tests and the probe name
    them) take the place of the launch's own choices from its shapes.
    `interpret` defaults from the backend, as `paged_attention`'s."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = not on_tpu
    elif interpret and on_tpu:
        raise ValueError(
            "selected_latent_attention(interpret=True) on the TPU backend: "
            "the kernel must run compiled there")
    page, width = pool.shape[1:]
    if pages_per_step is None:
        pages_per_step = pages_per_tile(page)
    pages = max(1, min(int(pages_per_step), block_table.shape[1]))
    rows = q_lat.shape[1] * q_lat.shape[2]
    fold = min(int(rows_per_fold or ROWS_PER_FOLD), rows)
    if rows % fold or fold % q_lat.shape[1]:
        raise ValueError(
            f"selected_latent_attention: {fold} rows a fold are no whole "
            f"heads of {q_lat.shape[1]} queries out of {rows} rows")
    return _walk_launch(q_lat, pool, block_table, seq_lens, keep,
                        scale=float(scale), rank=int(rank),
                        interpret=bool(interpret), pages=pages,
                        fold_rows=fold)


@functools.partial(jax.jit, static_argnames=("scale", "rank", "interpret",
                                             "pages", "fold_rows"))
def _walk_launch(q_lat, pool, block_table, seq_lens, keep, *, scale: float,
                 rank: int, interpret: bool, pages: int, fold_rows: int):
    """The launch as a jitted function of its own (`paged_attention.
    _paged_launch`'s reason: traced and lowered once a step program, not
    once a layer)."""
    b, s, h, width = q_lat.shape
    page = pool.shape[1]
    table_width = block_table.shape[1]
    rows, cols = h * s, pages * page
    tiles = -(-table_width // pages)
    # head-major query rows; the mask a tile at a time, one byte a pair
    q = q_lat.transpose(0, 2, 1, 3).reshape(b, rows, width)
    keep = jnp.pad(keep, ((0, 0), (0, 0),
                          (0, tiles * cols - keep.shape[-1])))
    keep = keep.reshape(b, s, tiles, cols).transpose(0, 2, 1, 3) \
        .astype(jnp.int8)
    # Mosaic's bounds checks of a copy's two ends are off, as in PR 56's
    # walk (most of a copy's scalar work), and the table is held to the
    # pool HERE, once a dispatch
    block_table = jnp.clip(block_table.astype(jnp.int32), 0,
                           pool.shape[0] - 1)

    def row(i, btab, slen):
        return i, 0, 0

    out = pl.pallas_call(
        functools.partial(_walk_kernel, page=page, scale=scale,
                          table_width=table_width, chunk=s, pages=pages,
                          rank=rank, fold_rows=fold_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[pl.BlockSpec((1, rows, width), row),
                      pl.BlockSpec((1, tiles, s, cols),
                                   lambda i, btab, slen: (i, 0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, rows, rank), row),
            scratch_shapes=[
                pltpu.VMEM((2, cols, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, rank), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, rows, rank), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            disable_bounds_checks=True,
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret, name="selected_latent_walk",
    )(block_table, seq_lens.reshape(b).astype(jnp.int32), q, keep, pool)
    return out.reshape(b, h, s, rank).transpose(0, 2, 1, 3)
