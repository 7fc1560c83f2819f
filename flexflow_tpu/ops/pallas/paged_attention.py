"""Fused PagedAttention kernel (Pallas TPU): flash-decoding over the
serving tier's paged KV pool, reading blocks IN PLACE through the
block table.

The gather formulation (`ops/attention.py _attend_decode_paged`, the
reference oracle) materializes a dense ``[slots, decode_max_seq, h, d]``
K/V view from the block pool every step, so per-step HBM traffic is
proportional to the TABLE WIDTH regardless of how many tokens are
actually live.  This kernel instead makes the block table part of the
kernel's index maps: grid ``(slots, table_width)`` with the table and
the per-slot sequence lengths as SCALAR-PREFETCH operands, so the K/V
BlockSpecs resolve ``(block_table[i, kb], 0, 0, 0)`` — Pallas's
pipeline DMAs exactly the physical pages a row owns, straight from the
pool's HBM layout, no dense view ever exists.

Block shapes (what Mosaic accepts for the pool layout
``[num_blocks, page, h, d]``): one grid step takes ALL local heads of
one physical page — K/V blocks ``(1, page, h, d)``, whose last two
dims equal the array's (the TPU rule: divisible by (8, 128) or equal
to the full dim; a per-head ``(1, page, 1, d)`` block is refused for
every h > 1).  Under `--serving-tp` the shard_map'd local pool is
``[nb, page, h/tp, d]`` and the same rule holds.  In VMEM the page is
swapped to ``[h, page, d]`` and both dots are batched over heads.

Traffic discipline: a row with ``pos`` tokens live owns
``pos // page + 1`` blocks.  Grid steps past that are mapped to the
row's LAST live block — a repeated block index, which Pallas's
pipeline elides (no re-fetch) — and their compute is skipped with
``pl.when``, so per-step HBM reads scale with live tokens, not
``decode_max_seq``.  Partial tail blocks and the scratch rows idle
slots park on (table all zeros, seq_len 0) are handled by the same
per-position mask the gather oracle uses: key positions past a row's
own length never enter the softmax.

Two entry points mirror the host-side twins (decoding.py):

  * ``paged_decode_attention`` — the seq-1 decode step;
  * ``paged_chunk_attention``  — the seq-C chunked-prefill step
    (``build_paged_chunk_step``): C queries per row, causal within the
    chunk via the mask ``key_pos <= pos + j``.  The gather twin's
    per-position scatter/gather/attend loop collapses into ONE kernel
    dispatch — the k/v scatter stays in plain JAX (it writes O(b*C*h*d)
    bytes, byte-identical to the oracle's), the kernel absorbs the
    read side.

Both accumulate the online softmax in f32 (m/l running columns + an
[h, s, d] accumulator in VMEM scratch carried across the kb grid
axis), like ops/pallas/flash_attention.py.  On the CPU backend the
same kernel runs under ``interpret=True`` — the parity tests
(tests/test_paged_kernel.py) execute the real kernel logic against
the gather oracle; on TPU it is always compiled by Mosaic, never
interpreted.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

_NEG_INF = -1e30

try:  # lazy-safe: CPU-only envs without pallas never touch the kernel
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _HAVE_PALLAS = True
except Exception:  # pragma: no cover
    _HAVE_PALLAS = False


def have_paged_kernel() -> bool:
    """Whether the fused kernel can be built at all in this runtime
    (config-time guard: selecting --paged-kernel pallas without this
    must raise ConfigError at BUILD time, never a deep ImportError
    mid-compile)."""
    return _HAVE_PALLAS


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


def _paged_kernel(btab_ref, slen_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, page: int, scale: float,
                  table_width: int, chunk: int):
    """One grid program = (row i, table column kb): fold the physical
    page `block_table[i, kb]` — all heads of it — into row i's online
    softmax."""
    i = pl.program_id(0)
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = slen_ref[i]
    live = _live_block_count(pos, chunk, page, table_width)

    @pl.when(kb < live)
    def _fold():
        q = q_ref[0]                      # [h, chunk, dk]
        k = jnp.swapaxes(k_ref[0], 0, 1)  # [page, h, dk] -> [h, page, dk]
        v = jnp.swapaxes(v_ref[0], 0, 1)  # [h, page, dv]
        if k.dtype != q.dtype:  # VMEM-tile cast (bf16 query, f32 pool)
            k = k.astype(q.dtype)
        s = jnp.einsum(
            "hsd,hpd->hsp", q, k, preferred_element_type=jnp.float32,
        ) * scale  # [h, chunk, page] f32
        # chunk token j attends key positions <= pos + j: causal within
        # the chunk, visible-prefix across steps — exactly the gather
        # oracle's mask, so partial tail blocks and scratch rows
        # (pos 0, all-zero table) fall out of the same comparison
        k_pos = kb * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        q_pos = pos + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
        m_prev = m_ref[...]               # [h, chunk, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.einsum(
            "hsp,hpd->hsd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(kb == table_width - 1)
    def _write():
        l = l_ref[...]
        l_safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def paged_attention(qh, k_pool, v_pool, block_table, seq_lens,
                    scale: float, *, interpret: Optional[bool] = None):
    """Fused paged attention over the pool.

    qh:          [b, s, h, dk]  this step's queries (s = 1 or chunk C)
    k_pool:      [num_blocks, page, h, dk]  the physical K pool
    v_pool:      [num_blocks, page, h, dv]
    block_table: [b, table_width] int32 (host-owned, scratch-padded)
    seq_lens:    [b] int32 — row i's incoming position (its chunk
                 occupies positions seq_lens[i] .. seq_lens[i]+s-1,
                 already scattered into the pool by the caller)
    ->           [b, s, h, dv] context, qh's dtype

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
    b, s, h, dk = qh.shape
    page, dv = k_pool.shape[1], v_pool.shape[-1]
    table_width = block_table.shape[1]
    qt = qh.transpose(0, 2, 1, 3)  # [b, h, s, dk]
    block_table = block_table.astype(jnp.int32)
    seq_lens = seq_lens.reshape(b).astype(jnp.int32)

    def q_map(i, kb, btab, slen):
        return i, 0, 0, 0

    def kv_map(i, kb, btab, slen):
        # out-of-range kb repeats the row's last live block: Pallas
        # elides the re-fetch, so HBM traffic follows live tokens
        live = _live_block_count(slen[i], s, page, table_width)
        return btab[i, jnp.minimum(kb, live - 1)], 0, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, table_width),
        in_specs=[
            pl.BlockSpec((1, h, s, dk), q_map),
            pl.BlockSpec((1, page, h, dk), kv_map),
            pl.BlockSpec((1, page, h, dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, h, s, dv), q_map),
        scratch_shapes=[
            pltpu.VMEM((h, s, 1), jnp.float32),   # running max
            pltpu.VMEM((h, s, 1), jnp.float32),   # running denominator
            pltpu.VMEM((h, s, dv), jnp.float32),  # context accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, page=page, scale=scale,
                          table_width=table_width, chunk=s),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, dv), qh.dtype),
        interpret=interpret,
        name="paged_attention",
    )(block_table, seq_lens, qt, k_pool, v_pool)
    return out.transpose(0, 2, 1, 3)


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
