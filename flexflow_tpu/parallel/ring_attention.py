"""Ring attention: sequence-parallel attention over an ICI ring.

The reference has NO context parallelism (SURVEY §5 — attention is one
cudnnMultiHeadAttnForward call; the closest capability is "Repartition
on the sequence dim + FFIterationConfig.seq_length").  This module is
the TPU-native instantiation of that capability slot: q/k/v arrive
sharded on the sequence dim over a mesh axis; K/V shards rotate around
the ring via `ppermute` while each device accumulates its queries'
online-softmax state — total memory O(s_local^2) and the transfers ride
ICI neighbor links (bandwidth-optimal on a torus axis).

Used by MultiHeadAttention when its inputs' seq dim is partitioned
(strategy inserts Repartition(dim=1)); lowered via `shard_map`.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

_NEG_INF = -1e30


def _block_attend(q, k, v, scale, mask):
    """One (q_block, kv_block) partial attention in f32 (dense path).

    q: [b, sq, h, d]; k/v: [b, sk, h, d]; mask: [sq, sk] bool or None.
    Returns (o_b [b, sq, h, d] normalized, lse_b [b, h, sq]); fully
    masked rows carry lse = -inf and o = 0 so the merge ignores them.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1)  # [b, h, sq]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    l_safe = jnp.where(l > 0.0, l, 1.0)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    o_b = pv / l_safe.transpose(0, 2, 1)[..., None]
    lse_b = jnp.where(l > 0.0, m + jnp.log(l_safe), _NEG_INF)
    return o_b, lse_b


def _block_attend_flash(q, k, v, scale, causal, interpret):
    """Flash-kernel block attend: the Pallas fwd kernel already returns
    (normalized out, lse) — exactly the merge state — so no [sq, sk]
    score tensor ever touches HBM.  `causal` uses the kernel's static
    intra-block masking (the ring's DIAGONAL blocks, where local and
    global positions coincide).  q: [b, sq, h, d]; k/v: [b, sk, h, d].
    """
    from ..ops.pallas import flash_attention as fa

    b, sq, h, d = q.shape
    sk = k.shape[1]

    def flat(t):
        return t.transpose(0, 2, 1, 3).reshape(b * h, t.shape[1], d)

    out, lse = fa._flash_fwd_pallas(
        flat(q), flat(k), flat(v), scale, causal,
        *fa._pick_blocks("fwd", sq, sk), interpret=interpret,
    )
    o_b = out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    return o_b.astype(jnp.float32), lse.reshape(b, h, sq)


def _use_flash_blocks(qh, kh, sp: int, block_impl: str) -> bool:
    """Decide on the PER-SHARD shapes (global seq / sp): the kernels
    run inside shard_map, so a globally-divisible length whose shard
    has no >=128 tile must still fall back to dense."""
    from ..ops.pallas import flash_attention as fa

    if block_impl == "dense":
        return False
    b, sq, h, d = qh.shape
    sk = kh.shape[1]
    ok = (
        fa._HAVE_PALLAS
        and sq % sp == 0
        and sk % sp == 0
        and fa._supported(
            jax.ShapeDtypeStruct((b * h, sq // sp, d), qh.dtype),
            jax.ShapeDtypeStruct((b * h, sk // sp, d), kh.dtype),
        )
    )
    if block_impl == "flash":
        # forced: a silent dense fallback would make callers (and the
        # equivalence test) believe they exercised the kernel
        if not ok:
            raise ValueError(
                f"block_impl='flash' unsupported here (pallas="
                f"{fa._HAVE_PALLAS}, global shapes {tuple(qh.shape)}/"
                f"{tuple(kh.shape)}, sp={sp} -> shard seqs "
                f"{sq // sp if sq % sp == 0 else 'indivisible'}/"
                f"{sk // sp if sk % sp == 0 else 'indivisible'})"
            )
        return True
    return ok and jax.default_backend() == "tpu"  # "auto"


def _ring_attention_sharded(qh, kh, vh, *, axis_name: str, sp: int,
                            scale: float, causal: bool):
    """DENSE per-shard body (inside shard_map); qh/kh/vh:
    [b, s_local, h, d].  Per-block state is (normalized out, lse),
    merged with an -inf-safe log-sum-exp reweighting.  This path
    differentiates through plain jax ops; it is the fallback for
    shapes the Pallas kernels cannot tile (and for non-square causal
    cross-attention) — supported rings, causal included, route through
    _ring_flash_trainable instead."""
    idx = jax.lax.axis_index(axis_name)
    s_local = qh.shape[1]
    k_local = kh.shape[1]  # may differ from s_local (cross-attention)
    b, _, h, d = qh.shape

    lse_acc = jnp.full((b, h, s_local), _NEG_INF, jnp.float32)
    o_acc = jnp.zeros((b, s_local, h, d), jnp.float32)

    k_blk, v_blk = kh, vh
    perm = [(i, (i + 1) % sp) for i in range(sp)]
    for step in range(sp):
        # the block we currently hold started at device (idx - step) % sp
        src = (idx - step) % sp
        if causal:
            # absolute-position causality (matches the dense path's
            # tril over [qlen, klen] global positions)
            q_pos = idx * s_local + jnp.arange(s_local)[:, None]
            k_pos = src * k_local + jnp.arange(k_local)[None, :]
            mask = q_pos >= k_pos  # [sq, sk]
        else:
            mask = None
        o_b, lse_b = _block_attend(qh, k_blk, v_blk, scale, mask)
        # log-sum-exp merge of normalized partials; -inf-safe (a row
        # with no live keys yet keeps lse -inf and zero output)
        lse_new = jnp.logaddexp(lse_acc, lse_b)
        live = lse_new > _NEG_INF / 2
        c_old = jnp.where(live, jnp.exp(lse_acc - lse_new), 0.0)
        c_new = jnp.where(live, jnp.exp(lse_b - lse_new), 0.0)
        o_acc = (
            o_acc * c_old.transpose(0, 2, 1)[..., None]
            + o_b * c_new.transpose(0, 2, 1)[..., None]
        )
        lse_acc = lse_new
        if step + 1 < sp:
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
    return o_acc.astype(qh.dtype)


def _ring_flash_fwd_sharded(qh, kh, vh, *, axis_name: str, sp: int,
                            scale: float, causal: bool, interpret: bool):
    """Flash ring FORWARD returning (out, lse) — the residuals the
    manual backward needs.

    Causality without kernel offsets: ring step 0 is every device's
    DIAGONAL block (src == idx), which is exactly the kernel's static
    causal masking; later steps hold strictly earlier (fully visible)
    or strictly later (fully masked) blocks, decided by the traced
    `step <= idx` — masked blocks simply don't merge (their compute is
    the inherent idle work of an unbalanced causal ring)."""
    idx = jax.lax.axis_index(axis_name)
    b, s_local, h, d = qh.shape
    lse_acc = jnp.full((b, h, s_local), _NEG_INF, jnp.float32)
    o_acc = jnp.zeros((b, s_local, h, d), jnp.float32)
    k_blk, v_blk = kh, vh
    perm = [(i, (i + 1) % sp) for i in range(sp)]
    for step in range(sp):
        o_b, lse_b = _block_attend_flash(
            qh, k_blk, v_blk, scale, causal and step == 0, interpret)
        if causal and step > 0:
            lse_b = jnp.where(step <= idx, lse_b, _NEG_INF)
        lse_new = jnp.logaddexp(lse_acc, lse_b)
        live = lse_new > _NEG_INF / 2
        c_old = jnp.where(live, jnp.exp(lse_acc - lse_new), 0.0)
        c_new = jnp.where(live, jnp.exp(lse_b - lse_new), 0.0)
        o_acc = (
            o_acc * c_old.transpose(0, 2, 1)[..., None]
            + o_b * c_new.transpose(0, 2, 1)[..., None]
        )
        lse_acc = lse_new
        if step + 1 < sp:
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
    return o_acc.astype(qh.dtype), lse_acc


def _ring_flash_bwd_sharded(qh, kh, vh, out, lse, dout, *,
                            axis_name: str, sp: int, scale: float,
                            causal: bool, interpret: bool):
    """Flash ring BACKWARD (causal via the same diagonal-step /
    gated-visibility scheme as the forward).

    Each device owns its q rows' (out, lse, dout) and accumulates dq
    locally with the Pallas dq kernel; dk/dv partial sums ROTATE WITH
    their k/v blocks (the dkv kernel adds each device's contribution
    as the block passes through), so after sp steps plus one homing
    ppermute every gradient block is complete on its owner.  The
    global softmax statistics ride in `lse` — each block's
    probabilities recompute against the FULL-sequence normalizer, which
    is what makes blockwise dk/dv sums exact."""
    from ..ops.pallas import flash_attention as fa

    b, s_local, h, d = qh.shape
    k_local = kh.shape[1]

    def flat(t):
        return t.transpose(0, 2, 1, 3).reshape(b * h, t.shape[1], d)

    q2, do2, o2 = flat(qh), flat(dout), flat(out)
    lse2 = lse.reshape(b * h, s_local)
    dq_bq, dq_bk = fa._pick_blocks("dq", s_local, k_local)
    dkv_bq, dkv_bk = fa._pick_blocks("dkv", s_local, k_local)

    def unflat(t2, s):
        return t2.reshape(b, h, s, d).transpose(0, 2, 1, 3)

    idx = jax.lax.axis_index(axis_name)
    dq_acc = jnp.zeros((b, s_local, h, d), jnp.float32)
    k_blk, v_blk = kh, vh
    dk_blk = jnp.zeros_like(kh, dtype=jnp.float32)
    dv_blk = jnp.zeros_like(vh, dtype=jnp.float32)
    perm = [(i, (i + 1) % sp) for i in range(sp)]
    for step in range(sp):
        # causal off-diagonal steps: this device's queries see the held
        # block only when it is strictly earlier (step <= idx).  Masked
        # blocks must not reach the kernel with the true lse: their raw
        # scores can EXCEED the global normalizer (they never entered
        # the softmax), and exp(s - lse) would overflow before the gate
        # zeroes it — feeding a huge lse drives p to exactly 0 instead.
        if causal and step > 0:
            live = step <= idx
            lse_in = jnp.where(live, lse2, jnp.float32(1e30))
            g = live.astype(jnp.float32)
        else:
            lse_in, g = lse2, jnp.float32(1.0)
        dq_b, dk_b, dv_b = fa._flash_bwd_pallas(
            q2, flat(k_blk), flat(v_blk), o2, lse_in, do2, scale,
            causal and step == 0,
            dq_bq, dq_bk, interpret=interpret,
            dkv_blocks=(dkv_bq, dkv_bk),
        )
        dq_acc = dq_acc + g * unflat(dq_b, s_local).astype(jnp.float32)
        dk_blk = dk_blk + g * unflat(dk_b, k_local).astype(jnp.float32)
        dv_blk = dv_blk + g * unflat(dv_b, k_local).astype(jnp.float32)
        # rotate the k/v blocks with their accumulating gradients; the
        # FINAL rotation homes each gradient block to its owner, so
        # only the accumulators ride it (k/v are dead after the last
        # kernel call)
        if step + 1 < sp:
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        dk_blk = jax.lax.ppermute(dk_blk, axis_name, perm)
        dv_blk = jax.lax.ppermute(dv_blk, axis_name, perm)
    return (dq_acc.astype(qh.dtype), dk_blk.astype(kh.dtype),
            dv_blk.astype(vh.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _ring_flash_trainable(qh, kh, vh, mesh, seq_axis, spec, sp, scale,
                          causal, interpret):
    return _ring_flash_trainable_fwd(qh, kh, vh, mesh, seq_axis, spec,
                                     sp, scale, causal, interpret)[0]


def _ring_flash_trainable_fwd(qh, kh, vh, mesh, seq_axis, spec, sp,
                              scale, causal, interpret):
    out, lse = jax.shard_map(
        functools.partial(_ring_flash_fwd_sharded, axis_name=seq_axis,
                          sp=sp, scale=scale, causal=causal,
                          interpret=interpret),
        mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=(spec, PartitionSpec(spec[0], spec[2], seq_axis)),
        check_vma=False,
    )(qh, kh, vh)
    return out, (qh, kh, vh, out, lse)


def _ring_flash_trainable_bwd(mesh, seq_axis, spec, sp, scale, causal,
                              interpret, res, dout):
    qh, kh, vh, out, lse = res
    lse_spec = PartitionSpec(spec[0], spec[2], seq_axis)
    dq, dk, dv = jax.shard_map(
        functools.partial(_ring_flash_bwd_sharded, axis_name=seq_axis,
                          sp=sp, scale=scale, causal=causal,
                          interpret=interpret),
        mesh=mesh,
        in_specs=(spec, spec, spec, spec, lse_spec, spec),
        out_specs=(spec, spec, spec),
        check_vma=False,
    )(qh, kh, vh, out, lse, dout)
    return dq, dk, dv


_ring_flash_trainable.defvjp(_ring_flash_trainable_fwd,
                             _ring_flash_trainable_bwd)


def ring_attention(
    qh,
    kh,
    vh,
    mesh: Mesh,
    seq_axis: str,
    *,
    batch_spec=None,
    head_spec=None,
    scale: float = 1.0,
    causal: bool = False,
    block_impl: str = "auto",
    training: bool = False,
):
    """Sequence-parallel attention on [b, s, h, d] arrays whose s dim is
    sharded over `seq_axis`.  batch_spec/head_spec name the mesh axes (or
    None) sharding the batch/head dims, so the shard_map specs match the
    surrounding SPMD sharding.

    block_impl: "auto" routes rings whose shard shapes the Pallas
    kernels can tile through the FLASH ring — fully differentiable via
    the manual ring backward (_ring_flash_trainable), O(tile) VMEM
    score blocks, no [sq, sk] HBM tensor in either pass; causal rings
    qualify too when shards are square (self-attention: the diagonal
    step uses the kernel's static causal mask, off-diagonal steps gate
    a traced visibility bit).  Everything else takes the dense jax-op
    path.  "dense" forces the dense path; "flash" forces the flash
    ring (raises when unsupported; interpret-mode off-TPU for tests).
    `training` is accepted for call-site symmetry but both paths
    differentiate."""
    sp = mesh.shape[seq_axis]
    spec = PartitionSpec(batch_spec, seq_axis, head_spec, None)
    if causal and qh.shape[1] != kh.shape[1]:
        # causal flash needs square diagonal blocks (self-attention)
        if block_impl == "flash":
            raise ValueError(
                "block_impl='flash' causal rings need equal q/k seq "
                f"lengths, got {qh.shape[1]}/{kh.shape[1]}")
        flash = False
    else:
        flash = _use_flash_blocks(qh, kh, sp, block_impl)
    if flash:
        return _ring_flash_trainable(
            qh, kh, vh, mesh, seq_axis, spec, sp, float(scale),
            bool(causal), jax.default_backend() != "tpu",
        )
    fn = functools.partial(
        _ring_attention_sharded,
        axis_name=seq_axis,
        sp=sp,
        scale=scale,
        causal=causal,
    )
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(qh, kh, vh)
