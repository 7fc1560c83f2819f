"""Flash attention kernels (Pallas TPU): forward AND backward.

TPU-native replacement for the reference's monolithic
cudnnMultiHeadAttnForward (/root/reference/src/ops/attention.cu:35): a
blockwise online-softmax attention kernel that never materializes the
[s, s] score matrix in HBM — scores live in VMEM tiles feeding the MXU.

Design:
  * layout [batch*heads, seq, head_dim]; grid (bh, q_blocks); K/V for
    one bh slice stay in VMEM (fine up to ~8k seq at d=64..128);
  * online softmax with running (m, l, acc) in f32, output written once;
  * causal masking skips fully-masked KV blocks via the loop bound;
  * backward: two Pallas kernels sharing the forward's tiling — a dq
    kernel (grid over q blocks, loop over kv) and a dkv kernel (grid
    over kv blocks, loop over q), both recomputing probabilities in
    VMEM from the saved log-sum-exp plus the precomputed
    delta = rowsum(dO * O), so no [s, s] residual ever touches HBM.

On the CPU backend (test meshes) the pure-jnp twin runs instead.  On
TPU a shape the tiling cannot cover also takes the jnp twin, but never
silently: `_supported` rejections warn with the shape (once per shape).
"""
from __future__ import annotations

import functools
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..op import remat_keep

# Per-kernel preferred (block_q, block_k): r5 on-chip ASYMMETRIC sweep
# (v5e, bh=96, d=64, seq2048, scan-chained timing so per-call dispatch
# is amortized — scripts/flash_ceiling_probe.py, table in docs/PERF.md).
# Each kernel wants the LOOPED axis wide (fewer grid revisits of the
# resident operand) and the GRID axis narrow:
#   fwd  (grid q, loop kv): (512, 2048) — 4.41ms vs 5.55 at 1024x1024;
#   dq   (grid q, loop kv): (512, 1024) — 5.79ms vs 7.10;
#   dkv  (grid kv, loop q): (2048, 512) — 7.35ms vs 7.57.
# bq=2048 tiles fail to compile for fwd/dq (f32 score tile + q-block
# accumulators crowd VMEM); the dkv kernel fits them because its
# per-cell state is [bk, d].
_PREFERRED = {"fwd": (512, 2048), "dq": (512, 1024), "dkv": (2048, 512)}

_NEG_INF = -1e30


def _largest_dividing(s: int, cap: int) -> Optional[int]:
    b = cap
    while b >= 128:
        if s % b == 0 and s >= b:
            return b
        b //= 2
    return None


def _pick_block(s: int) -> Optional[int]:
    """Generic feasibility tile (supportedness checks); per-kernel
    choices come from _pick_blocks."""
    return _largest_dividing(s, 1024)


def _pick_blocks(kernel: str, sq: int, sk: int) -> Tuple[int, int]:
    cap_q, cap_k = _PREFERRED[kernel]
    return _largest_dividing(sq, cap_q), _largest_dividing(sk, cap_k)


def _ref_attention(q, k, v, scale: float, causal: bool):
    """Reference jnp path: q,k,v [bh, s, d] -> out [bh, sq, d]."""
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool))  # absolute positions: q_i sees k_0..k_i
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                scale: float, causal: bool, seq_k: int):
    q = q_ref[0]  # [bq, d] — native dtype feeds the MXU; accumulate f32
    block_q = q.shape[0]
    j = pl.program_id(1)
    q_start = j * block_q

    m = jnp.full((block_q,), _NEG_INF, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    acc = jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32)  # [bq, dv]

    num_k = seq_k // block_k
    if causal:
        # KV blocks entirely past the last query row contribute nothing
        # (q_start is traced — program_id — so clamp with jnp)
        num_k_live = (q_start + block_q + block_k - 1) // block_k
        num_k = jnp.minimum(num_k, num_k_live)

    def body(kb, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk] f32
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        correction = jnp.exp(m - m_new)
        l_new = l * correction + jnp.sum(p, axis=-1)
        acc_new = acc * correction[:, None] + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, num_k, body, (m, l, acc))
    l_safe = jnp.where(l > 0.0, l, 1.0)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # lse buffer is one full [1, 1, sq] row revisited across q blocks;
    # write just this block's slice (block shape (1,1,sq) satisfies the
    # TPU tiling rule by equaling the array dims)
    lse_ref[0, 0, pl.ds(q_start, block_q)] = m + jnp.log(l_safe)


try:  # pallas import is lazy-safe: CPU-only envs never touch the kernel
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _HAVE_PALLAS = True
except Exception:  # pragma: no cover
    _HAVE_PALLAS = False


def _flash_fwd_pallas(q, k, v, scale: float, causal: bool,
                      block_q: int, block_k: int, interpret: bool = False):
    bh, sq, d = q.shape
    sk, dv = v.shape[1:]
    grid = (bh, sq // block_q)
    kernel = functools.partial(
        _fwd_kernel, block_k=block_k, scale=scale, causal=causal, seq_k=sk
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, sk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, sk, dv), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, sq), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
        **_resident_vmem(k, v),
    )(q, k, v)
    return out, lse.reshape(bh, sq)


#: whole-row operands a grid cell keeps in VMEM (K and V of the forward
#: and dq kernels, Q and dO of the dkv kernel), double-buffered, up to
#: which the 16 MiB a kernel may use without asking hold them beside the
#: score tiles; past it the call asks for `_RESIDENT_VMEM_BYTES` (8,192
#: keys of 256 with values of 128 are 12 MiB double-buffered)
_RESIDENT_FREE_BYTES = 4 << 20
_RESIDENT_VMEM_BYTES = 64 << 20


def _resident_vmem(*rows) -> dict:
    """`pallas_call` keywords for kernels that hold `rows` ([bh, s, d]
    each) a grid cell: nothing where the default VMEM holds them (every
    shape before PR 43 lowers as it did), else a larger limit."""
    held = 2 * sum(r.shape[1] * r.shape[2] * jnp.dtype(r.dtype).itemsize
                   for r in rows)
    if held <= _RESIDENT_FREE_BYTES:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=_RESIDENT_VMEM_BYTES)}


def _supported(q, k, block_q: Optional[int] = None,
               block_k: Optional[int] = None, v=None) -> bool:
    if not _HAVE_PALLAS:
        return False
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = block_q or _pick_block(sq)
    block_k = block_k or _pick_block(sk)
    dv = d if v is None else v.shape[-1]
    return (
        block_q is not None
        and block_k is not None
        and sq % block_q == 0
        and sk % block_k == 0
        and (d % 128 == 0 or d == 64)  # lane-dim friendly head sizes
        # values as wide as the keys, or of whole lane tiles of their own
        and (dv == d or (d % 128 == 0 and dv % 128 == 0))
        and sq >= block_q
        and sk >= block_k
    )


def _use_pallas(q, k, v=None) -> bool:
    """Pallas kernels on the TPU backend (inside jit tracing array
    placement is unknown, so decide by backend).  A shape `_supported`
    rejects there takes the [s, s] jnp twin VISIBLY: the kernel was
    asked for (seq >= flash_min_seq), so the switch warns with the
    shape — Python's warning registry shows it once per shape."""
    if jax.default_backend() != "tpu":
        return False
    if _supported(q, k, v=v):
        return True
    warnings.warn(
        f"flash attention: no Pallas tiling for q{tuple(q.shape)} "
        f"k{tuple(k.shape)}"
        + (f" v{tuple(v.shape)}" if v is not None else "")
        + " (need seq divisible by a 128..1024 power-of-two block and "
        "a head width of 64 or a multiple of 128 for q and k; values of "
        "the same width, or both widths multiples of 128: `flash_mha` "
        "pads q and k to that); running the dense [s, s] jnp path on "
        "TPU instead")
    return False


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, scale: float, causal: bool):
    """q, k [bh, s, d], v [bh, sk, dv] -> [bh, sq, dv].  Pallas on TPU,
    jnp on CPU."""
    out, _ = _flash_fwd(q, k, v, scale, causal)
    return out


def _flash_fwd(q, k, v, scale, causal):
    if _use_pallas(q, k, v):
        return _flash_fwd_pallas(
            q, k, v, scale, causal,
            *_pick_blocks("fwd", q.shape[1], k.shape[1]),
        )
    # reference path: also produce lse for the backward
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool))  # absolute positions: q_i sees k_0..k_i
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1)
    l = jnp.sum(jnp.exp(s - m[..., None]), axis=-1)
    out = jnp.einsum(
        "bqk,bkd->bqd",
        (jnp.exp(s - m[..., None]) / l[..., None]).astype(v.dtype),
        v,
    )
    return out, m + jnp.log(l)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, block_k: int, scale: float, causal: bool, seq_k: int):
    q = q_ref[0]             # [bq, d]
    do = do_ref[0]           # [bq, d]
    lse = lse_ref[0, 0]      # [bq] f32 (arrays carried [bh, 1, sq]:
    delta = delta_ref[0, 0]  # the TPU block rule wants 3-D tiles)
    block_q, d = q.shape
    j = pl.program_id(1)
    q_start = j * block_q

    num_k = seq_k // block_k
    if causal:
        num_k = jnp.minimum(
            num_k, (q_start + block_q + block_k - 1) // block_k
        )

    def body(kb, acc):
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk]
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta[:, None]) * scale).astype(k_blk.dtype)
        return acc + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    acc = jax.lax.fori_loop(
        0, num_k, body, jnp.zeros((block_q, d), jnp.float32)
    )
    dq_ref[0] = acc.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref,
                    *, block_q: int, scale: float, causal: bool, seq_q: int):
    k = k_ref[0]  # [bk, d]
    v = v_ref[0]  # [bk, d]
    block_k, d = k.shape
    kb = pl.program_id(1)
    k_start = kb * block_k

    num_q = seq_q // block_q
    # causal: q blocks strictly before this kv block are fully masked
    jb_start = k_start // block_q if causal else 0

    def body(jb, carry):
        dk_acc, dv_acc = carry
        q_blk = q_ref[0, pl.ds(jb * block_q, block_q), :]
        do_blk = do_ref[0, pl.ds(jb * block_q, block_q), :]
        lse_blk = lse_ref[0, 0, pl.ds(jb * block_q, block_q)]
        delta_blk = delta_ref[0, 0, pl.ds(jb * block_q, block_q)]
        s = jax.lax.dot_general(
            q_blk, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk]
        if causal:
            q_pos = jb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse_blk[:, None])  # [bq, bk] f32
        pt = p.astype(do_blk.dtype)
        dv_acc = dv_acc + jax.lax.dot_general(
            pt, do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, d]
        dp = jax.lax.dot_general(
            do_blk, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        ds = (p * (dp - delta_blk[:, None]) * scale).astype(q_blk.dtype)
        dk_acc = dk_acc + jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, d]
        return dk_acc, dv_acc

    dk_acc, dv_acc = jax.lax.fori_loop(
        jb_start, num_q, body, (jnp.zeros((block_k, d), jnp.float32),
                                jnp.zeros(v.shape, jnp.float32)))
    dk_ref[0] = dk_acc.astype(dk_ref.dtype)
    dv_ref[0] = dv_acc.astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, out, lse, dout, scale, causal,
                      block_q: int, block_k: int, interpret: bool = False,
                      dkv_blocks: Optional[Tuple[int, int]] = None):
    """block_q/block_k tile the dq kernel; dkv_blocks (defaulting to
    the same pair) tiles the dkv kernel — the two kernels' best tiles
    are opposite-handed (see _PREFERRED)."""
    bh, sq, d = q.shape
    sk, dv = v.shape[1:]
    dkv_bq, dkv_bk = dkv_blocks or (block_q, block_k)
    # delta = rowsum(dO * O): one cheap fused jnp pass, shared by both
    # kernels (standard flash-backward preprocessing)
    delta = jnp.sum(
        dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    ).reshape(bh, 1, sq)  # f32; [bh, 1, sq] satisfies the 3-D tile rule
    lse = lse.astype(jnp.float32).reshape(bh, 1, sq)

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, block_k=block_k, scale=scale, causal=causal,
            seq_k=sk,
        ),
        grid=(bh, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, sk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, sk, dv), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_q, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=interpret,
        name="flash_attention_bwd_dq",
        **_resident_vmem(k, v),
    )(q, k, v, dout, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, block_q=dkv_bq, scale=scale, causal=causal,
            seq_q=sq,
        ),
        grid=(bh, sk // dkv_bk),
        in_specs=[
            pl.BlockSpec((1, sq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, dkv_bk, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, dkv_bk, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, sq, dv), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, sq), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, sq), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, dkv_bk, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, dkv_bk, dv), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, dv), v.dtype),
        ],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
        **_resident_vmem(q, dout),
    )(q, k, v, dout, lse, delta)
    return dq, dk, dv


def _flash_vjp_fwd(q, k, v, scale, causal):
    # under `remat` the forward kernel is not run again for these two
    out, lse = map(remat_keep, _flash_fwd(q, k, v, scale, causal))
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(scale, causal, res, dout):
    q, k, v, out, lse = res
    if _use_pallas(q, k, v):
        sq, sk = q.shape[1], k.shape[1]
        return _flash_bwd_pallas(
            q, k, v, out, lse, dout, scale, causal,
            *_pick_blocks("dq", sq, sk),
            dkv_blocks=_pick_blocks("dkv", sq, sk),
        )
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    dof = dout.astype(jnp.float32)
    s = jnp.einsum("bqd,bkd->bqk", qf, kf) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool))  # absolute positions: q_i sees k_0..k_i
        s = jnp.where(mask, s, _NEG_INF)
    p = jnp.exp(s - lse[..., None])  # recomputed probabilities
    dv = jnp.einsum("bqk,bqd->bkd", p, dof)
    dp = jnp.einsum("bqd,bkd->bqk", dof, vf)
    delta = jnp.sum(dof * out.astype(jnp.float32), axis=-1)  # [bh, sq]
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bqk,bkd->bqd", ds, kf)
    dk = jnp.einsum("bqk,bqd->bkd", ds, qf)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def mha_flash(qh, kh, vh, scale: float, causal: bool):
    """q, k [b, s, h, d], v [b, sk, h, dv] -> [b, sq, h, dv]."""
    b, sq, h, d = qh.shape
    sk, dv = kh.shape[1], vh.shape[-1]
    q2 = qh.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    k2 = kh.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    v2 = vh.transpose(0, 2, 1, 3).reshape(b * h, sk, dv)
    o = flash_attention(q2, k2, v2, scale, causal)
    return o.reshape(b, h, sq, dv).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# One-tile kernels: the same algorithm tiled for SHORT rows (PR 33).
#
# Everything above is built for long rows: online softmax with a running
# (m, l, acc), a fori_loop over KV blocks, a [bh, s, d] layout (four
# transposes a layer; d = 64 fills half the lanes) and a backward in two
# kernels that each recompute the probabilities.  When the whole key row
# fits one VMEM tile (sk <= ONE_TILE_MAX_KV) none of that is needed:
#
#   * block (bq, sk) with sk the FULL key length, plain softmax in f32
#     (the v5e's vector unit has no bf16), no rescale, no loop;
#   * q, k, v are read straight from the [b, s, h*d] projection output
#     through a 128-lane block: two heads of 64, or one of >= 128.  A
#     head is picked out of the pair by zeroing the other's lanes of ONE
#     operand of each product, which costs the MXU nothing (a 64-deep
#     contraction fills half of the 128-deep array either way) and keeps
#     every vector op lane-dense;
#   * ONE backward kernel: S and P recomputed once from the saved
#     log-sum-exp, dq, dk and dv emitted together (5 products a tile, one
#     read of q, k, v, dO), delta = rowsum(dO * O) inside it;
#   * the pallas_calls sit in jitted functions, so a step of N layers
#     lowers one kernel body a direction, not N.
#
# `pick_tiling` chooses between the two from what can be observed (key
# length, head dim, backend); `flash_mha` is the [b, s, h, d] entry the
# attention op calls.  Nothing above this line changed.
# ---------------------------------------------------------------------------

#: longest key row the one-tile kernels take
ONE_TILE_MAX_KV = 1024
#: f32 score elements a grid cell holds at most: one [512, 1024] tile,
#: 2 MB.  The larger the tile the better (my chip runs, PR 33, forward +
#: backward at kv 512 / 1,024, ms: 0.664 / 1.951 at 64 Ki elements,
#: 0.571 / 1.951 at 128 Ki, 0.523 / 1.718 at 256 Ki, - / 1.643 at 512 Ki;
#: working through a block 128 or 256 rows at a time gained nothing)
_ONE_TILE_ELEMS = 512 * 1024
#: the backward kernel's tile with its f32 temporaries passes the 16 MiB
#: a kernel may use by default; the v5e's VMEM holds 128 MiB
_ONE_TILE_VMEM_BYTES = 64 << 20


def pick_tiling(sk: int, d: int, backend: Optional[str] = None) -> str:
    """Which attention core a key length and head dim get on a backend:
    "one_tile" (whole-row Pallas kernels), "online" (the long-row Pallas
    kernels above) or "jnp" (the twin: off-TPU, or a head dim neither
    tiling covers).  A pure function of its arguments."""
    backend = backend or jax.default_backend()
    if backend != "tpu" or not _HAVE_PALLAS:
        return "jnp"
    if not (d == 64 or d % 128 == 0):
        return "jnp"
    if sk <= ONE_TILE_MAX_KV and sk % 128 == 0:
        return "one_tile"
    return "online"


def _one_tile_block_q(sq: int, sk: int) -> Optional[int]:
    cap = 128
    while cap < 1024 and 2 * cap * sk <= _ONE_TILE_ELEMS:
        cap *= 2
    return _largest_dividing(sq, cap)


def _one_tile_supported(qh, kh, vh) -> bool:
    """[b, s, h, d] shapes the one-tile kernels cover: heads pack into
    whole 128-lane blocks and the query length divides into blocks."""
    _, sq, h, d = qh.shape
    w = max(d, 128)
    return (vh.shape[-1] == d and (h * d) % w == 0
            and _one_tile_block_q(sq, kh.shape[1]) is not None)


def _scale_folds(scale: float) -> bool:
    """A power-of-two scale multiplies bf16 q exactly, which saves one
    multiply per score element."""
    return float(np.frexp(scale)[0]) == 0.5


def _head_lanes(rows: int, w: int, d: int):
    return jax.lax.broadcasted_iota(jnp.int32, (rows, w), 1) // d


def _one_tile_scores(qj, k, scale, fold, causal, q_start):
    s = jax.lax.dot_general(
        qj, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [bq, sk] f32
    if not fold:
        s = s * scale
    if causal:
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
    return s


def _one_tile_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, d: int,
                         scale: float, causal: bool):
    q, k, v = q_ref[0], k_ref[0], v_ref[0]  # [bq, w], [sk, w], [sk, w]
    bq, w = q.shape
    heads = w // d
    fold = _scale_folds(scale)
    if fold:
        q = q * jnp.asarray(scale, q.dtype)
    q_start = pl.program_id(2) * bq
    lane = _head_lanes(bq, w, d) if heads > 1 else None
    out = None
    for j in range(heads):
        qj = q if heads == 1 else jnp.where(lane == j, q, jnp.zeros_like(q))
        s = _one_tile_scores(qj, k, scale, fold, causal, q_start)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[:, None])
        l = jnp.sum(p, axis=-1)
        oj = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) / l[:, None]  # [bq, w]: head j's lanes are its output
        out = oj if heads == 1 else (
            jnp.where(lane == j, oj, 0.0 if out is None else out))
        lse_ref[0, 0, j, :] = m + jnp.log(l)
    o_ref[0] = out.astype(o_ref.dtype)


def _one_tile_bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                         dq_ref, dk_ref, dv_ref, *acc, d: int, scale: float,
                         causal: bool, num_q: int):
    q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    bq, w = q.shape
    sk = k.shape[0]
    heads = w // d
    fold = _scale_folds(scale)
    qs = q * jnp.asarray(scale, q.dtype) if fold else q
    qi = pl.program_id(2)
    do_o = do.astype(jnp.float32) * o_ref[0].astype(jnp.float32)  # [bq, w]
    if heads > 1:
        lane_q, lane_k = _head_lanes(bq, w, d), _head_lanes(sk, w, d)
    dq = jnp.zeros((bq, w), jnp.float32)
    dk = jnp.zeros((sk, w), jnp.float32)
    dv = jnp.zeros((sk, w), jnp.float32)
    for j in range(heads):
        if heads == 1:
            qj, kj, doj, delta = qs, k, do, jnp.sum(do_o, axis=-1)
        else:
            # head j's lanes of ONE operand a product: the other head's
            # lanes then add nothing to S and dP, and dq, dk, dv come
            # out zero outside head j's lanes, so they simply add up
            mq, mk = lane_q == j, lane_k == j
            qj = jnp.where(mq, qs, jnp.zeros_like(qs))
            doj = jnp.where(mq, do, jnp.zeros_like(do))
            kj = jnp.where(mk, k, jnp.zeros_like(k))
            delta = jnp.sum(jnp.where(mq, do_o, 0.0), axis=-1)
        s = _one_tile_scores(qj, k, scale, fold, causal, qi * bq)
        p = jnp.exp(s - lse_ref[0, 0, j, :][:, None])  # [bq, sk] f32
        dv = dv + jax.lax.dot_general(
            p.astype(do.dtype), doj, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [sk, w]
        dp = jax.lax.dot_general(
            doj, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, sk]
        # the scale of dS is applied to the [*, w] results instead
        ds = (p * (dp - delta[:, None])).astype(q.dtype)
        dq = dq + jax.lax.dot_general(
            ds, kj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, w]
        dk = dk + jax.lax.dot_general(
            ds, qj, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [sk, w]; qj carries the scale when it folds
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)
    if not fold:
        dk = dk * scale
    if num_q == 1:
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)
        return
    dk_acc, dv_acc = acc  # f32 [sk, w], carried over the q blocks

    @pl.when(qi == 0)
    def _():
        dk_acc[...] = dk
        dv_acc[...] = dv

    @pl.when(qi > 0)
    def _():
        dk_acc[...] += dk
        dv_acc[...] += dv

    @pl.when(qi == num_q - 1)
    def _():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _one_tile_specs(q, k, d: int):
    b, sq, e = q.shape
    sk = k.shape[1]
    w = max(d, 128)
    bq = _one_tile_block_q(sq, sk)
    grid = (b, e // w, sq // bq)
    q_spec = pl.BlockSpec((1, bq, w), lambda i, g, j: (i, j, g))
    kv_spec = pl.BlockSpec((1, sk, w), lambda i, g, j: (i, 0, g))
    lse_spec = pl.BlockSpec((1, 1, w // d, bq), lambda i, g, j: (i, g, 0, j))
    lse_shape = jax.ShapeDtypeStruct((b, e // w, w // d, sq), jnp.float32)
    return grid, q_spec, kv_spec, lse_spec, lse_shape


@functools.partial(jax.jit,
                   static_argnames=("d", "scale", "causal", "interpret"))
def _one_tile_fwd(q, k, v, *, d: int, scale: float, causal: bool,
                  interpret: bool = False):
    """q [b, sq, h*d], k, v [b, sk, h*d] -> (out [b, sq, h*d],
    lse [b, h*d/w, w/d, sq] f32)."""
    grid, q_spec, kv_spec, lse_spec, lse_shape = _one_tile_specs(q, k, d)
    return pl.pallas_call(
        functools.partial(_one_tile_fwd_kernel, d=d, scale=scale,
                          causal=causal),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), lse_shape],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_ONE_TILE_VMEM_BYTES),
        interpret=interpret,
        name="one_tile_attention_fwd",
    )(q, k, v)


@functools.partial(jax.jit,
                   static_argnames=("d", "scale", "causal", "interpret"))
def _one_tile_bwd(q, k, v, out, lse, dout, *, d: int, scale: float,
                  causal: bool, interpret: bool = False):
    """-> (dq, dk, dv), one kernel; dk and dv accumulate over the query
    blocks where sq needs more than one."""
    grid, q_spec, kv_spec, lse_spec, _ = _one_tile_specs(q, k, d)
    num_q = grid[2]
    sk, w = kv_spec.block_shape[1:]
    return pl.pallas_call(
        functools.partial(_one_tile_bwd_kernel, d=d, scale=scale,
                          causal=causal, num_q=num_q),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec, lse_spec],
        out_specs=[q_spec, kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((sk, w), jnp.float32)] * 2
        if num_q > 1 else [],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_ONE_TILE_VMEM_BYTES),
        interpret=interpret,
        name="one_tile_attention_bwd",
    )(q, k, v, out, dout, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def one_tile_attention(q, k, v, d: int, scale: float, causal: bool):
    """q [b, sq, h*d], k, v [b, sk, h*d] -> [b, sq, h*d]; heads of
    width d side by side on the last dim, as the projections leave
    them.  Pallas only: `flash_mha` picks it on the TPU."""
    return _one_tile_fwd(q, k, v, d=d, scale=scale, causal=causal)[0]


def _one_tile_vjp_fwd(q, k, v, d, scale, causal):
    out, lse = map(remat_keep, _one_tile_fwd(
        q, k, v, d=d, scale=scale, causal=causal))
    return out, (q, k, v, out, lse)


def _one_tile_vjp_bwd(d, scale, causal, res, dout):
    return _one_tile_bwd(*res, dout, d=d, scale=scale, causal=causal)


one_tile_attention.defvjp(_one_tile_vjp_fwd, _one_tile_vjp_bwd)


def lane_width(d: int) -> int:
    """The width a head of d channels is given on the lanes: 64 stays,
    anything else goes up to whole 128-lane tiles."""
    return d if d == 64 else -(-d // 128) * 128


def flash_mha(qh, kh, vh, scale: float, causal: bool):
    """q, k [b, s, h, d], v [b, sk, h, dv] -> [b, sq, h, dv]: the
    attention core without a [b, h, s, s] tensor in HBM, tiled by
    `pick_tiling`.

    Keys wider than the values (latent attention without a query
    bottleneck: 192 against 128) go through the long-row kernels, q and
    k padded with zero channels to whole lane tiles (192 -> 256), which
    adds nothing to a score.  The MXU of the v5e contracts 128 channels
    a pass, so 192 costs the two passes 256 does; the pad's price is
    the bytes of q, k, dq and dk (a third more of each), not products.
    The values keep their own width, so `p v` and its three backward
    products run at 128 (`_fwd_kernel`: the accumulator takes v's
    width)."""
    b, sq, h, d = qh.shape
    sk = kh.shape[1]
    if vh.shape[-1] != d:
        pad = ((0, 0),) * 3 + ((0, lane_width(d) - d),)
        return mha_flash(jnp.pad(qh, pad), jnp.pad(kh, pad), vh, scale,
                         causal)
    if (pick_tiling(sk, d) == "one_tile"
            and _one_tile_supported(qh, kh, vh)):
        o = one_tile_attention(
            qh.reshape(b, sq, h * d), kh.reshape(b, sk, h * d),
            vh.reshape(b, sk, h * d), d, scale, causal)
        return o.reshape(b, sq, h, d)
    return mha_flash(qh, kh, vh, scale, causal)
