"""Fused PagedAttention kernel (Pallas TPU): flash-decoding over the
serving tier's paged KV pool, reading blocks IN PLACE through the
block table.

The gather formulation (`ops/attention.py _attend_decode_paged`, the
reference oracle) materializes a dense ``[slots, decode_max_seq, h, d]``
K/V view from the block pool every step, so per-step HBM traffic
follows the TABLE WIDTH whatever is live.  This kernel instead makes
the block table part of its index maps: grid
``(slots, table_width / P)`` with the table and the per-slot sequence
lengths as SCALAR-PREFETCH operands, and P K and P V BlockSpecs a grid
program (``PAGES_PER_STEP``), the p-th of which resolves
``(block_table[i, kb * P + p], 0, 0, 0)`` — Pallas's pipeline DMAs
exactly the physical pages a row owns, straight from the pool's HBM
layout, no dense view ever exists.  Every shape and the grid are
static: how many tokens are live is data, so one program serves every
mix of lengths.

Block shapes (what Mosaic accepts for the pool layout
``[num_blocks, page, h, d]``): a K/V block is ALL local heads of one
physical page, ``(1, page, h, d)``, whose last two dims equal the
array's (the TPU rule: divisible by (8, 128) or equal to the full dim;
a per-head ``(1, page, 1, d)`` block is refused for every h > 1).
Under `--serving-tp` the shard_map'd local pool is
``[nb, page, h/tp, d]`` and the same rule holds.  (Leaving THIS pool in
HBM and copying pages by hand, with a trip count that follows the
row's length, is refused by Mosaic for d = 64: a slice of a ref whose
minor dim is padded to 128 lanes.)

A HEAD-MAJOR pool ``[num_blocks, h, page, d]`` (a grouped layer's:
`MultiHeadAttentionParams.kv_head_major`, laguna's d = 128) is read
that way (`_head_major_kernel`, PR 56): grid ``(slots,)``, one program
a row, the K and V pools passed whole with ``memory_space=pl.ANY``, and
inside the program a ``fori_loop`` over the row's live pages N at a
time (`pages_per_tile`: as many as keep the K and V tiles, two of each,
within 4 MiB of VMEM; 32 at laguna's 32 KB a page).  A block there is
one contiguous, lane-aligned slab ``[h, page, 128]`` (page = 16 is
bf16's sublane tile), which Mosaic accepts as a copy's source; each of
a tile's 2 N copies lands where its keys fall in the tile's buffer
``[h, N * page, d]``, so a head's keys of the whole tile are ONE matrix
and the fold (`_fold_head_major`: a product batched over the key/value
heads, nothing computed for another head's pair) pays its mask, its
rescale of the online softmax and its MXU round trips once a tile.
Tile j + 1's copies are started before tile j is folded.  The trip
count is ``ceil(live / N)``: no step exists for a column past a row's
live pages, and a parked row walks its one scratch page.  The spare
columns of a row's LAST tile repeat its last live block (their keys are
masked by position; every byte the fold reads was written by a copy,
so no product meets stale VMEM).

The math stays in that layout and both products run on the MXU: a
page ``[page, h, d]`` is, with no data movement, the matrix
``[page * h, d]`` (row c is key c // h of head c % h; h = 16 is bf16's
sublane tile), the queries ``[s, h, d]`` likewise ``[s * h, d]``.  ONE
product contracting d scores a fold's pages for every pair of heads,
the mask keeps an entry where the heads match (grouped-query heads:
where the query head's key/value head, `q_head // G`, is the key's;
one program for every G) and the key is visible,
the online softmax runs over the lanes of that one f32 matrix, and ONE
product with ``v [n * page * h, dv]`` gives the context (another
head's probability is exp(-1e30) = 0).  The MXU does h times the
useful multiply-adds to spare the vector unit, which from PR 28 to
PR 42 widened, multiplied and reduced the whole page a query (0.30 us
a page; until PR 28 pages were transposed to head-major: 0.47).  A
fold is a chain of MXU round trips, so it takes several pages at once
(`_paged_kernel`): one page a fold read 0.42 us a page (PERF.md, PR 42).

Traffic discipline: a row with ``pos`` tokens live owns
``pos // page + 1`` blocks.  Table columns past that are mapped to the
row's LAST live block — a repeated block index, which Pallas's
pipeline elides (no re-fetch) — and their compute is skipped with
``pl.when``, so per-step HBM reads scale with live tokens, not
``decode_max_seq``.  What does NOT scale with live tokens is the grid:
``slots * table_width / P`` programs of ``2 P + 1`` index maps each,
0.03 ms a launch at cell 3's 16 x 64 and 0.05 at cell 7's 16 x 20,
most of either's launch (PERF.md, PR 42).  Where a row's table is
narrow and the launch reads for a chunk of queries
(`pages_per_program`: cell 7's prefill program, 20 columns), ONE
program takes the whole row: the columns past its live pages are then
fetched too (a table that narrow has few), and the read of a prefill
dispatch takes 9.6 ms where three programs a row took 11.0 (PERF.md,
PR 43).  Partial tail blocks and the
scratch rows idle slots park on (table all zeros, seq_len 0) fall to
the gather oracle's own per-position mask: key positions past a row's
length never enter the softmax.

Two entry points mirror the host-side twins (decoding.py):
``paged_decode_attention``, the seq-1 decode step, and
``paged_chunk_attention``, the seq-C chunked-prefill step
(``build_paged_chunk_step``): C queries a row, causal within the chunk
via ``key_pos <= pos + j``, ONE dispatch where the gather twin loops
over positions; the k/v scatter stays in plain JAX, byte-identical to
the oracle's.  Both carry the online softmax in f32 VMEM scratch over
the kb grid axis (the head-major walk: over its tiles).  On the CPU the
kernel runs under ``interpret=True``
(tests/test_paged_kernel.py); on TPU Mosaic always compiles it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

_NEG_INF = -1e30

#: physical pages one grid program folds (paged_attention) where it does
#: not take a row's whole table: the grid is (rows, table_width / this),
#: so a dispatch's fixed cost follows it, and a row's live pages are
#: fetched this many at a time
PAGES_PER_STEP = 8
#: the widest table ONE grid program takes whole, a row: up to three
#: steps' worth of columns the steps saved outweigh the fetches of the
#: columns past a row's live pages (each one block; measured at cell 7's
#: 20 columns: `pages_per_program`); cell 3's 64 columns with a page or
#: two live stay stepped, where a dead step costs nothing
_ROW_PAGES = 3 * PAGES_PER_STEP
#: and only where those K and V pages, double-buffered by the pipeline,
#: leave room in the 16 MiB of VMEM a kernel may use without asking
#: (cell 7's 20 pages of [16, 16, 128] bf16 are 5 MiB)
_ROW_VMEM_BYTES = 6 << 20
#: what the head-major walk's K and V tiles, two of each, may take of it
_TILE_VMEM_BYTES = 4 << 20

try:  # lazy-safe: CPU-only envs without pallas never touch the kernel
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _HAVE_PALLAS = True
except Exception:  # pragma: no cover
    _HAVE_PALLAS = False


def have_paged_kernel() -> bool:
    """Whether the fused kernel can be built at all in this runtime
    (asking for the kernel by name without this is a ConfigError at
    engine build, serving/scheduler.py pick_paged_read, never a deep
    ImportError mid-compile)."""
    return _HAVE_PALLAS


def pages_per_program(table_width: int, page_bytes: int, chunk: int) -> int:
    """Pages a grid program folds, from the launch's shapes alone: the
    row's whole table where the launch reads for a chunk of queries, the
    table is narrow (`_ROW_PAGES`) and its K and V pages (`page_bytes`
    a pair) fit `_ROW_VMEM_BYTES` double-buffered: one program a row, a
    third of the grid steps at cell 7's 20 columns; else
    `PAGES_PER_STEP`, which keeps the fetches of a wide table to its
    live pages.

    With ONE query a row the whole-row launch is the faster alone too
    (0.064 against 0.073 ms at cell 7's mean, scripts/paged_read_probe.py)
    and the slower in its program: a decode step is bound by its weights'
    bytes, the slices XLA prefetches hide under the read, and a read
    1.4 ms shorter a dispatch left 2.6 ms more of them waited for
    (`unnamed | slice-done`; `decode.device_ms.capacity` 39.8 -> 40.9),
    where the prefill program, with eight times the work a weight byte,
    got 1.6 ms shorter (`prefill.device_ms.capacity` 42.8 -> 41.2; my
    chip runs, PR 43)."""
    if (chunk > 1 and table_width <= _ROW_PAGES
            and 2 * table_width * page_bytes <= _ROW_VMEM_BYTES):
        return table_width
    return min(PAGES_PER_STEP, table_width)


def pages_per_tile(page_bytes: int) -> int:
    """Pages a tile of the head-major walk holds (`_head_major_kernel`),
    from the launch's shapes alone: as many as keep the K and V tiles,
    two of each in flight (`page_bytes` a K and V pair), within
    `_TILE_VMEM_BYTES`."""
    return max(1, _TILE_VMEM_BYTES // (2 * page_bytes))


def _live_block_count(pos, chunk: int, page: int, table_width: int):
    """Blocks row(s) at position `pos` touch when attending a chunk of
    `chunk` tokens: positions 0..pos+chunk-1 inclusive, clamped to the
    table.  Works on scalars and arrays (host telemetry + in-kernel)."""
    last = jnp.minimum(pos + chunk - 1, table_width * page - 1)
    return last // page + 1


def blocks_read(seq_lens: np.ndarray, live_mask: np.ndarray, chunk: int,
                page: int, table_width: int) -> int:
    """Host-side telemetry twin of the kernel's traffic discipline:
    physical KV blocks ONE fused dispatch streams for the rows
    `live_mask` marks live.  Idle rows count 0 — their single
    scratch-block fetch is a repeated index the pipeline elides, and
    excluding it keeps the counter a clean live-work signal (the
    convention ContinuousScheduler's serving/paged_kernel_* counters
    use; the scan-based prefill program is `chunk` seq-1 dispatches,
    accounted by summing this with chunk=1 per scan position).  The
    dense-gather equivalent is always ``len(seq_lens) *
    table_width``."""
    pos = np.asarray(seq_lens, np.int64)
    last = np.minimum(pos + chunk - 1, table_width * page - 1)
    per_row = np.where(np.asarray(live_mask, bool), last // page + 1, 0)
    return int(per_row.sum())


def scan_blocks_read(seq_lens: np.ndarray, counts: np.ndarray,
                     page: int, table_width: int) -> int:
    """`blocks_read` for a program that SCANS the seq-1 read: row i
    runs `counts[i]` positions starting at `seq_lens[i]` (0 = the row
    is idle or rides along on scratch), each a seq-1 dispatch over the
    row's prefix so far.  A decode dispatch is counts in {0, 1}; the
    scanned prefill and verify programs count up to their chunk."""
    pos = np.asarray(seq_lens, np.int64)[:, None]
    counts = np.asarray(counts, np.int64)[:, None]
    j = np.arange(max(int(counts.max(initial=0)), 1))[None, :]
    last = np.minimum(pos + j, table_width * page - 1)
    return int(np.where(j < counts, last // page + 1, 0).sum())


def _mxu(a, b, contract, batch=((), ())):
    """a x b over `contract` on the MXU (a product a `batch` dim: the
    heads of a head-major fold), f32 out: bf16 products are exact there;
    f32 operands (the parity tests') ask for f32's."""
    dt = jnp.promote_types(a.dtype, b.dtype)
    return jax.lax.dot_general(
        a.astype(dt), b.astype(dt), (contract, batch),
        precision=jax.lax.Precision.HIGHEST if dt == jnp.float32 else None,
        preferred_element_type=jnp.float32)


def _context(pr, v, batch=((), ())):
    """pr [.., m, n] f32 x v [.., n, dv] (a leading head dim with
    `batch`), the probabilities NOT rounded: an f32 is the sum of three
    bf16, so over a bf16 pool the three parts ride ONE product stacked
    on its rows, every term exact, summed in f32."""
    rows = pr.ndim - 2
    contract = ((rows + 1,), (rows,))
    if v.dtype != jnp.bfloat16:
        return _mxu(pr, v, contract, batch)
    hi = pr.astype(v.dtype)
    mid = (pr - hi).astype(v.dtype)
    lo = (pr - hi - mid).astype(v.dtype)
    hi, mid, lo = jnp.split(_mxu(jnp.concatenate([hi, mid, lo], axis=rows),
                                 v, contract, batch), 3, axis=rows)
    return hi + mid + lo


def _fold_pages(q_ref, ks, vs, m_ref, l_ref, acc_ref, col, pos, *,
                page: int, scale: float):
    """Fold the physical pages of table columns `col`, `col` + 1, ..
    into the row's online softmax.  ks, vs: a [page, h, d] each, as the
    pool holds them; the queries' `hq` heads are `hq / h` to a
    key/value head (grouped-query attention), query head g on key/value
    head `g // (hq / h)`."""
    chunk, hq, _ = q_ref.shape[1:]
    h = ks[0].shape[1]
    rows = chunk * hq
    # a page as the matrix it already is: row c of [page * h, d] is
    # key c // h of head c % h; scores [chunk * h, pages * page * h]
    k, v = (jnp.concatenate([x.reshape(page * h, -1) for x in xs])
            for xs in (ks, vs))
    s = _mxu(q_ref[0].reshape(rows, -1), k, ((1,), (1,))) * scale
    # an entry is a score where its two heads match; chunk token j
    # attends key positions <= pos + j: causal within the chunk,
    # visible-prefix across steps — the gather oracle's mask, so tail
    # blocks and scratch rows (pos 0, zero table) fall out of it too
    r = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    c = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    q_head = r % hq if hq == h else (r % hq) // (hq // h)
    keep = (q_head == c % h) & (col * page + c // h <= pos + r // hq)
    s = jnp.where(keep, s, _NEG_INF)
    m_prev = m_ref[...].reshape(rows, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    pr = jnp.exp(s - m_new)      # another head's entry: exp(-1e30) = 0
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = (l_ref[...].reshape(rows, 1) * corr + jnp.sum(
        pr, axis=1, keepdims=True)).reshape(l_ref.shape)
    acc_ref[...] = (acc_ref[...].reshape(rows, -1) * corr
                    + _context(pr, v)).reshape(acc_ref.shape)
    m_ref[...] = m_new.reshape(m_ref.shape)


def _paged_kernel(btab_ref, slen_ref, q_ref, *refs, page: int,
                  scale: float, table_width: int, chunk: int, pages: int):
    """One grid program = (row i, table columns kb*pages ..
    kb*pages+pages-1): fold up to `pages` physical pages of row i, all
    heads of each at once (`_fold_pages`), into the row's online
    softmax."""
    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * pages:]
    i = pl.program_id(0)
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = slen_ref[i]
    live = _live_block_count(pos, chunk, page, table_width)

    # a fold is one chain of MXU round trips and lane reductions, and
    # costs a page alone what it costs several: fold at once as many of
    # the program's pages as keep the scores, [chunk * h, n * page * h]
    # f32, within the 64 vector registers of 1,024 (a page of the group
    # past the live ones is all future keys: masked)
    group = max(1, min(pages, 65536 // (
        chunk * q_ref.shape[2] * page * k_refs[0].shape[2])))
    for p in range(0, pages, group):
        col = kb * pages + p

        @pl.when(col < live)
        def _fold(p=p, col=col):
            _fold_pages(q_ref, [r[0] for r in k_refs[p:p + group]],
                        [r[0] for r in v_refs[p:p + group]], m_ref, l_ref,
                        acc_ref, col, pos, page=page, scale=scale)

    @pl.when(kb == pl.num_programs(1) - 1)
    def _write():
        l = l_ref[...]
        l_safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def _fold_head_major(q_ref, k, v, m_ref, l_ref, acc_ref, col, pos, *,
                     page: int, scale: float, chunk: int):
    """`_fold_pages` for a HEAD-MAJOR pool: k, v are `[h, n * page, d]`,
    the pages of table columns `col` .. `col + n - 1` a head, q_ref
    `[1, h, G * chunk, d]` with a key/value head's G query
    heads side by side (row `g * chunk + t` is query head `j * G + g`,
    chunk token t).  A key/value head's queries against ITS keys alone,
    `[h, G * chunk, n * page]` scores by one product batched over the
    heads, so nothing is computed for another head's pair (the page-major fold multiplies
    and exponentiates every pair and masks `h - 1` of `h` away: at 8
    key/value heads under 48 query heads and a chunk of 32 that was
    36 ms a launch; PERF.md, PR 55)."""
    rows, cols = q_ref.shape[2], k.shape[1]
    # chunk token t attends key positions <= pos + t (`_fold_pages`)
    if chunk == 1:
        tok = 0
    elif chunk % 8 == 0:  # (rows / chunk, chunk, cols) merges for free
        tok = jax.lax.broadcasted_iota(
            jnp.int32, (rows // chunk, chunk, cols), 1).reshape(rows, cols)
    else:
        tok = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0) % chunk
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    keep = col * page + c <= pos + tok
    # every head in ONE batched product a stage (a loop over the heads
    # is a chain of small products, each waiting for the last: 1.0 us a
    # page at 8 heads and a chunk of 32; PERF.md, PR 55)
    heads = ((0,), (0,))
    s = _mxu(q_ref[0], k, ((2,), (2,)), heads) * scale  # [h, rows, cols]
    s = jnp.where(keep[None], s, _NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    pr = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(pr, axis=2, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + _context(pr, v, heads)
    m_ref[...] = m_new


def _head_major_kernel(btab_ref, slen_ref, q_ref, k_hbm, v_hbm, o_ref,
                       k_buf, v_buf, sems, m_ref, l_ref, acc_ref, *,
                       page: int, scale: float, table_width: int,
                       chunk: int, pages: int):
    """One grid program = one row: walk the row's live pages `pages` at
    a time (a tile).  The pools stay in HBM; a tile's pages are copied
    by hand into `k_buf` / `v_buf` `[2, h, pages * page, d]`, each page
    where its keys fall in the tile, so the fold reads a head's keys of
    the whole tile as one matrix; tile j + 1's copies are in flight
    while tile j is folded (`_fold_head_major`), and the trip count is
    the row's length."""
    i = pl.program_id(0)
    pos = slen_ref[i]
    live = _live_block_count(pos, chunk, page, table_width)
    tiles = pl.cdiv(live, pages)

    def copies(tile, slot):
        # a column past the row's live pages repeats its last live
        # block (its keys are masked by position): every byte the fold
        # reads was written by a copy, so no product meets stale VMEM
        for p in range(pages):
            block = btab_ref[i, jnp.minimum(tile * pages + p, live - 1)]
            where = pl.ds(p * page, page)
            yield pltpu.make_async_copy(
                k_hbm.at[block], k_buf.at[slot, :, where], sems.at[0, slot])
            yield pltpu.make_async_copy(
                v_hbm.at[block], v_buf.at[slot, :, where], sems.at[1, slot])

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
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
        _fold_head_major(q_ref, k_buf[slot], v_buf[slot], m_ref, l_ref,
                         acc_ref, j * pages, pos, page=page, scale=scale,
                         chunk=chunk)

    jax.lax.fori_loop(0, tiles, tile_step, None)
    l = l_ref[...]
    o_ref[0] = (acc_ref[...] / jnp.where(l > 0.0, l, 1.0)).astype(o_ref.dtype)


def paged_attention(qh, k_pool, v_pool, block_table, seq_lens,
                    scale: float, *, interpret: Optional[bool] = None,
                    pages_per_step: Optional[int] = None,
                    head_major: bool = False):
    """Fused paged attention over the pool.

    qh:          [b, s, h * G, dk]  this step's queries (s = 1 or chunk
                 C): G query heads to a key/value head, query head g
                 reads key/value head g // G (G = 1: one head count)
    k_pool:      [num_blocks, page, h, dk]  the physical K pool
    v_pool:      [num_blocks, page, h, dv]
    block_table: [b, table_width] int32 (host-owned, scratch-padded)
    seq_lens:    [b] int32 — row i's incoming position (its chunk
                 occupies positions seq_lens[i] .. seq_lens[i]+s-1,
                 already scattered into the pool by the caller)
    ->           [b, s, h * G, dv] context, qh's dtype

    `head_major`: the pools are `[num_blocks, h, page, d]` (a head's
    rows of a page side by side: `MultiHeadAttentionParams.
    kv_head_major`), and the kernel walks each row's live pages itself
    and folds a key/value head's queries against its own keys
    (`_head_major_kernel`): what a grouped layer's pool takes.

    Every shape and the grid are static in (b, s, table_width): how
    many tokens are live is DATA (`seq_lens`), so one program serves
    every mix of lengths.

    `pages_per_step` (the tests and the probe name one) takes the place
    of the launch's own choice from its shapes: pages a grid program
    folds (`pages_per_program`), pages a tile of the head-major walk
    (`pages_per_tile`).

    `interpret` defaults from the backend: the Mosaic-compiled kernel
    on TPU, the Pallas interpreter on CPU (the parity-test vehicle).
    Interpreting on TPU is refused — a run that asked for the kernel
    must never quietly time the interpreter."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = not on_tpu
    elif interpret and on_tpu:
        raise ValueError(
            "paged_attention(interpret=True) on the TPU backend: the "
            "kernel must run compiled there")
    b, s, hq, dk = qh.shape
    h, page = k_pool.shape[1:3][::1 if head_major else -1]
    dv = v_pool.shape[-1]
    if hq % h:
        raise ValueError(
            f"paged_attention: {hq} query heads are no multiple of the "
            f"pool's {h} key/value heads")
    table_width = block_table.shape[1]
    if pages_per_step is None:  # (the tests and the probe name one)
        page_bytes = page * h * (dk + dv) * k_pool.dtype.itemsize
        pages_per_step = pages_per_tile(page_bytes) if head_major \
            else pages_per_program(table_width, page_bytes, s)
    pages = max(1, min(int(pages_per_step), table_width))
    return _paged_launch(qh, k_pool, v_pool, block_table, seq_lens,
                         scale=float(scale), interpret=bool(interpret),
                         pages=pages, head_major=bool(head_major))


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "pages",
                                             "head_major"))
def _paged_launch(qh, k_pool, v_pool, block_table, seq_lens, *,
                  scale: float, interpret: bool, pages: int,
                  head_major: bool = False):
    """`paged_attention`'s launch as a jitted function of its own: the
    layers of a model call it with the same shapes, so the kernel is
    traced and lowered to Mosaic ONCE a step program and not once a
    layer (48 times in cell 7's programs: a whole-row prefill kernel,
    ten folds unrolled, took 24 s of set-up that way against the
    stepped one's 11; PERF.md PR 43); XLA inlines the calls."""
    b, s, hq, dk = qh.shape
    h, page = k_pool.shape[1:3][::1 if head_major else -1]
    dv = v_pool.shape[-1]
    table_width = block_table.shape[1]
    block_table = block_table.astype(jnp.int32)
    seq_lens = seq_lens.reshape(b).astype(jnp.int32)

    if head_major:
        # a key/value head's G query heads side by side: [b, h, G * s, d]
        g = hq // h
        rows = g * s
        qm = qh.reshape(b, s, h, g, dk).transpose(0, 2, 3, 1, 4).reshape(
            b, h, rows, dk)

        def row(i, btab, slen):
            return i, 0, 0, 0

        # a copy is ~11 scalar bundles and Mosaic's bounds checks of its
        # two ends 14 more, and the walk issues 2 N of them a tile ahead
        # of a fold it cannot overlap: 0.97 ms a launch without them
        # against 1.17 (PERF.md, PR 56).  The checks are off and the
        # table is held to the pool HERE, once a dispatch (XLA shares it
        # between the layers of a pass)
        block_table = jnp.clip(block_table, 0, k_pool.shape[0] - 1)
        out = pl.pallas_call(
            functools.partial(_head_major_kernel, page=page, scale=scale,
                              table_width=table_width, chunk=s, pages=pages),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(b,),
                in_specs=[pl.BlockSpec((1, h, rows, dk), row),
                          pl.BlockSpec(memory_space=pl.ANY),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((1, h, rows, dv), row),
                scratch_shapes=[
                    pltpu.VMEM((2, h, pages * page, dk), k_pool.dtype),
                    pltpu.VMEM((2, h, pages * page, dv), v_pool.dtype),
                    pltpu.SemaphoreType.DMA((2, 2)),
                    pltpu.VMEM((h, rows, 1), jnp.float32),
                    pltpu.VMEM((h, rows, 1), jnp.float32),
                    pltpu.VMEM((h, rows, dv), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((b, h, rows, dv), qh.dtype),
            compiler_params=pltpu.CompilerParams(disable_bounds_checks=True),
            interpret=interpret, name="paged_attention_head_major",
        )(block_table, seq_lens, qm, k_pool, v_pool)
        return out.reshape(b, h, g, s, dv).transpose(0, 3, 1, 2, 4).reshape(
            b, s, hq, dv)

    def q_map(i, kb, btab, slen):
        return i, 0, 0, 0

    # a column past the row's live pages repeats its LAST live block:
    # Pallas elides the re-fetch, so HBM traffic follows live tokens.
    # The clamp is applied to the table HERE, once a dispatch (XLA
    # shares it between the layers of a pass): the scalar core then
    # evaluates 2 * pages index maps a grid step, each one SMEM load
    # (PERF.md PR 28: a fifth of a short row's cost with it in the maps)
    steps = -(-table_width // pages)
    live = _live_block_count(seq_lens, s, page, table_width)
    cols = jnp.arange(steps * pages, dtype=jnp.int32)[None, :]
    block_table = jnp.take_along_axis(
        block_table, jnp.minimum(cols, live[:, None] - 1), axis=1)

    def kv_map(p):
        def index(i, kb, btab, slen):
            return btab[i, kb * pages + p], 0, 0, 0
        return index

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, steps),
        in_specs=[pl.BlockSpec((1, s, hq, dk), q_map)]
        + [pl.BlockSpec((1, page, h, dk), kv_map(p)) for p in range(pages)]
        + [pl.BlockSpec((1, page, h, dv), kv_map(p)) for p in range(pages)],
        out_specs=pl.BlockSpec((1, s, hq, dv), q_map),
        scratch_shapes=[
            pltpu.VMEM((s, hq, 1), jnp.float32),   # running max
            pltpu.VMEM((s, hq, 1), jnp.float32),   # running denominator
            pltpu.VMEM((s, hq, dv), jnp.float32),  # context accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, page=page, scale=scale,
                          table_width=table_width, chunk=s, pages=pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s, hq, dv), qh.dtype),
        interpret=interpret,
        name="paged_attention",
    )(block_table, seq_lens, qh, *([k_pool] * pages), *([v_pool] * pages))


def paged_decode_attention(qh, k_pool, v_pool, block_table, seq_lens,
                           scale: float, *,
                           interpret: Optional[bool] = None):
    """The seq-1 decode twin: qh [b, 1, h, dk] -> [b, 1, h, dv]."""
    assert qh.shape[1] == 1, "decode twin takes one query per row"
    return paged_attention(qh, k_pool, v_pool, block_table, seq_lens,
                           scale, interpret=interpret)


def paged_chunk_attention(qh, k_pool, v_pool, block_table, seq_lens,
                          scale: float, *,
                          interpret: Optional[bool] = None):
    """The seq-C chunked-prefill twin: qh [b, C, h, dk], causal within
    the chunk -> [b, C, h, dv]."""
    return paged_attention(qh, k_pool, v_pool, block_table, seq_lens,
                           scale, interpret=interpret)
