"""Multi-head latent attention (MLA) with decoupled, YaRN-scaled RoPE.

    c_q = RMS(h W_qa);  q = c_q W_qb -> heads of [q_nope | q_rope]
    [c_kv | k_r] = h W_kva;  c_kv <- RMS(c_kv)
    RoPE on q_rope (per head) and on k_r (ONE vector for all heads)
    [k_nope | v]_head = c_kv W_kvb
    score = (q_nope . k_nope + q_rope . k_r) * s, causal softmax,
    out = concat_heads(sum p v) W_o

What a token leaves behind is `(c_kv, RoPE(k_r))`: `kv_lora_rank +
qk_rope_head_dim` values a layer (576 at the published sizes, against
`2 x heads x head_dim` = 16,384 for full keys and values).

A third published variant puts a CONSTANT on each bottleneck
(`q_lora_scale` s_q, `kv_lora_scale` s_kv; 1.0 = none):
`q = (c_q W_qb) s_q` (so on q_nope and on q_rope before its rotation)
and `c_kv <- RMS(c_kv) s_kv` (so on k_nope and on v, not on k_r).  Both
are linear in the normed bottleneck, so both formulations apply them
where the bottleneck's norm applies its gain, in float32, before the
one rounding to the compute precision: `RMS(.) * (gain * s)`.  The
paged pool therefore HOLDS `(RMS(c_kv) s_kv, RoPE(k_r))` and the
absorbed products are the unscaled block's.

Two formulations, one set of weights:

* no cache (`decode_max_seq == 0`): keys and values are EXPANDED from
  the latent, `k_h = [k_nope_h | k_r]` (192 wide at the published
  sizes) against values of `v_head_dim` (128), and the causal core over
  the step's own tokens is `MultiHeadAttention`'s choice again: from
  `flash_min_seq` keys up the flash kernels
  (`ops/pallas/flash_attention.py flash_mha`, which pads q and k to
  whole lane tiles and keeps the values' width), so that no `[s, s]`
  tensor a head exists forward or backward; below it einsum, softmax,
  einsum.  The graph a trainer or a one-shot forward runs;
* paged latent cache (`decode_max_seq`, `kv_page_size`,
  `kv_num_blocks`): the state is ONE pool `latent_cache [num_blocks,
  page, rank + rope]` plus the host-owned `block_table` / `seq_lens`
  every paged op carries.  A step of s tokens a row writes their
  latents at the row's own positions `seq_lens[i] + j` and attends
  with `W_kvb` ABSORBED: the queries are taken into the latent space
  (`q_nope W_kvb_k^T`), scores and the weighted sum run on the
  gathered `[slots, max_seq, rank + rope]` view, built ONCE a step,
  and the heads' values come out of the latent at the end.  s == 1 is
  the decode step; s == C is a prefill chunk in one pass over the
  weights (decoding.build_paged_prefill_pass), query j masked to
  `key_pos <= seq_lens[i] + j`.  The pool is read by gather only:
  `kv_kernel` "gather" and "pallas" are the same formulation here (no
  in-place latent kernel is written; Mosaic refused a 64-wide slice in
  PR 28, and the rope part is 64 wide).

Two published variants of the block change the weights or the
rotation, both read from `MLAParams`: `q_lora_rank == 0` has NO query
bottleneck (one `wq [e, heads, nope + rope]` stands where `wq_a`,
`q_norm` and `wq_b` do), and `nope` rotates nothing (the 64 "rope"
channels of q and the shared `k_r` enter the score as they are: the
model's other layers carry the order of the tokens).

Positions arrive as the op's second input (an op without positions
takes x alone); RoPE angles are computed from them in float32.  The
published code de-interleaves the rope channels before rotating half
against half; rotating adjacent pairs `(2i, 2i+1)` as here gives the
same scores.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..fftype import DataType, OperatorType
from ..initializer import (DEFAULT_WEIGHT_INIT, ConstantInitializer,
                           ZeroInitializer)
from ..obs.scopes import scope
from ..tensor import ParallelDim, ParallelTensorShape
from .norm import rms_normalize
from .op import Op, ShapeError, WeightSpec
from .rope import yarn_frequencies as _yarn_frequencies


@dataclasses.dataclass(frozen=True)
class MLAParams:
    embed_dim: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float = 10000.0
    # YaRN (rope_scaling of the published config); factor 1 = plain RoPE
    rope_factor: float = 1.0
    rope_original_max: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    eps: float = 1e-5
    #: no position enters the score: q_rope and k_r are not rotated
    nope: bool = False
    #: constants on the query's and on the key/value bottleneck after
    #: their norms (module docstring); 1.0 is the block without them
    q_lora_scale: float = 1.0
    kv_lora_scale: float = 1.0

    @property
    def latent_width(self) -> int:
        """Values a token leaves in the cache, a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(p: MLAParams) -> np.ndarray:
    """Rotation per position of each adjacent pair of rope channels,
    [qk_rope_head_dim / 2] float64 (`ops/rope.py yarn_frequencies` at
    this op's sizes)."""
    return _yarn_frequencies(p.qk_rope_head_dim, p.rope_theta, p.rope_factor,
                             p.rope_original_max, p.beta_fast, p.beta_slow)


def softmax_scale(p: MLAParams) -> float:
    """(nope + rope)^-0.5 * mscale(all_dim)^2."""
    m = yarn_mscale(p.rope_factor, p.mscale_all_dim)
    return (p.qk_nope_head_dim + p.qk_rope_head_dim) ** -0.5 * m * m


def rope(x, positions, p: MLAParams):
    """Rotate adjacent channel pairs of x [b, s, ..., d] by
    positions [b, s] x the YaRN frequencies, in float32; cos and sin
    are scaled by mscale / mscale(all_dim), 1 at the published
    values."""
    ratio = (yarn_mscale(p.rope_factor, p.mscale)
             / yarn_mscale(p.rope_factor, p.mscale_all_dim))
    angle = (positions.astype(jnp.float32)[..., None]
             * jnp.asarray(yarn_frequencies(p), jnp.float32))
    angle = angle.reshape(angle.shape[:2] + (1,) * (x.ndim - 3)
                          + angle.shape[-1:])
    cos, sin = jnp.cos(angle) * ratio, jnp.sin(angle) * ratio
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class MLAttention(Op):
    """See the module docstring.  The paged pool `latent_cache` holds,
    a token, `(RMS(c_kv) * kv_lora_scale, RoPE(k_r))` in the compute
    precision.  Paged, `forward` takes the step's
    length from its input: seq 1 traces the decode step
    (`_attend_paged`), seq C a prefill chunk in one pass
    (`_attend_paged_chunk`), which is what lets a family built on this
    op carry `prefill_pass`; GPT's attention keeps per-position
    shapes instead, for the byte equality its scanned prefill gives."""

    op_type = OperatorType.MLA_ATTENTION

    def __init__(self, params, inputs, name="", shard=None,
                 decode_max_seq: int = 0, kv_page_size: int = 0,
                 kv_num_blocks: int = 0, kv_kernel: str = "gather"):
        from .op import ShardConfig

        # must exist before Op.__init__ runs make_weight_specs
        self._decode_max_seq = int(decode_max_seq)
        self._kv_page_size = int(kv_page_size)
        self._kv_num_blocks = int(kv_num_blocks)
        self._kv_kernel = str(kv_kernel or "gather")
        super().__init__(params, inputs, name=name,
                         shard=shard or ShardConfig())

    def _paged(self) -> bool:
        return self._decode_max_seq > 0

    def ctor_kwargs(self) -> dict:
        if not self._paged():
            return {}
        return {"decode_max_seq": self._decode_max_seq,
                "kv_page_size": self._kv_page_size,
                "kv_num_blocks": self._kv_num_blocks,
                "kv_kernel": self._kv_kernel}

    def cache_entries(self):
        return ("latent_cache",) if self._paged() else ()

    def infer_output_shapes(self, input_shapes):
        x, *pos = input_shapes
        xd = [d for d in x.dims if not d.is_replica_dim]
        if len(pos) != (0 if self.params.nope else 1):
            raise ShapeError(
                f"{self.name}: a rotating op takes x and positions, one "
                f"without positions (nope) x alone; got {len(pos) + 1} "
                "inputs")
        if len(xd) != 3 or any(q.logical_shape != x.logical_shape[:2]
                               for q in pos):
            raise ShapeError(
                f"{self.name}: expect x [batch, seq, embed] and positions "
                f"[batch, seq], got {x.logical_shape} and "
                f"{[q.logical_shape for q in pos]}")
        if xd[1].degree != 1 or xd[2].degree != 1 \
                or not self.shard.is_trivial():
            raise ShapeError(
                f"{self.name}: latent attention is sharded over the "
                "batch only (heads over a model axis are not built yet)")
        if self.params.qk_rope_head_dim % 2:
            raise ShapeError(f"{self.name}: rope width must be even")
        if self.params.q_lora_scale != 1.0 and not self.params.q_lora_rank:
            raise ShapeError(f"{self.name}: q_lora_scale without a query "
                             "bottleneck (q_lora_rank 0)")
        return [x]

    def _query_weights(self) -> int:
        """Weights that make the queries: wq_a, q_norm, wq_b, or one wq."""
        return 3 if self.params.q_lora_rank else 1

    def num_trainable_weights(self) -> int:
        return self._query_weights() + 4

    def make_weight_specs(self, input_shapes):
        x = input_shapes[0]
        p: MLAParams = self.params
        xd = [d for d in x.dims if not d.is_replica_dim]
        rep = ParallelDim(1, x.total_degree, is_replica_dim=True)

        def w(*sizes, dtype=x.dtype, replica=rep):
            return ParallelTensorShape(
                tuple(ParallelDim(s) for s in sizes) + (replica,), dtype)

        init, one = DEFAULT_WEIGHT_INIT, ConstantInitializer(1.0)
        e, h = p.embed_dim, p.num_heads
        dq = p.qk_nope_head_dim + p.qk_rope_head_dim
        specs = ([
            WeightSpec("wq_a", w(e, p.q_lora_rank), init),
            WeightSpec("q_norm", w(p.q_lora_rank), one),
            WeightSpec("wq_b", w(p.q_lora_rank, h, dq), init),
        ] if p.q_lora_rank else [WeightSpec("wq", w(e, h, dq), init)]) + [
            WeightSpec("wkv_a", w(e, p.latent_width), init),
            WeightSpec("kv_norm", w(p.kv_lora_rank), one),
            WeightSpec("wkv_b", w(p.kv_lora_rank, h,
                                  p.qk_nope_head_dim + p.v_head_dim), init),
            WeightSpec("wo", w(h, p.v_head_dim, e), init),
        ]
        if not self._paged():
            return specs
        n, page, nb = (self._decode_max_seq, self._kv_page_size,
                       self._kv_num_blocks)
        if xd[0].degree != 1:
            raise ShapeError(
                f"{self.name}: paged decode needs an unsharded batch dim "
                "(slots are host-owned)")
        if page < 1 or n % page:
            raise ShapeError(
                f"{self.name}: kv_page_size {page} must divide "
                f"decode_max_seq {n}")
        if nb < 2:
            raise ShapeError(
                f"{self.name}: kv_num_blocks {nb} < 2 (block 0 is the "
                "scratch block idle slots write into)")
        zero, one_rep = ZeroInitializer(), ParallelDim(
            1, 1, is_replica_dim=True)
        return specs + [
            WeightSpec("latent_cache",
                       w(nb, page, p.latent_width, replica=one_rep), zero),
            WeightSpec("block_table",
                       w(xd[0].size, n // page, dtype=DataType.INT32,
                         replica=one_rep), zero),
            WeightSpec("seq_lens",
                       w(xd[0].size, dtype=DataType.INT32,
                         replica=one_rep), zero),
        ]

    # -- forward --------------------------------------------------------
    def forward(self, inputs, weights, *, training=False, rng=None):
        x, *positions = inputs
        p: MLAParams = self.params
        nq = self._query_weights()
        wkv_a, kv_norm, wkv_b, wo = weights[nq:nq + 4]
        dn, rk = p.qk_nope_head_dim, p.kv_lora_rank

        def turn(t):
            return t if p.nope else rope(t, positions[0], p)

        def gain(g, s):
            # a bottleneck's constant rides its norm's gain (float32
            # there); 1.0 leaves the gain, and the program, as it was
            return g if s == 1.0 else g.astype(jnp.float32) * s

        with scope("proj"):
            if p.q_lora_rank:
                wq_a, q_norm, wq_b = weights[:3]
                cq = rms_normalize(jnp.matmul(x, wq_a),
                                   gain(q_norm, p.q_lora_scale), p.eps)
                q = jnp.einsum("bsr,rhd->bshd", cq, wq_b)
            else:
                q = jnp.einsum("bse,ehd->bshd", x, weights[0])
            q_nope, q_rope = q[..., :dn], turn(q[..., dn:])
            kv = jnp.matmul(x, wkv_a)
            latent = jnp.concatenate(
                [rms_normalize(kv[..., :rk],
                               gain(kv_norm, p.kv_lora_scale), p.eps),
                 turn(kv[..., rk:])], axis=-1)  # [b, s, rk + dr]
        if self._paged():
            pool, btab, slen = weights[nq + 4:]
            if x.shape[1] > 1:
                with scope("paged_read"):
                    ctx, pool = self._attend_paged_chunk(
                        q_nope, q_rope, latent, wkv_b, pool, btab, slen)
                with scope("out"):
                    out = jnp.einsum("bshd,hde->bse", ctx, wo)
                    return [out.astype(x.dtype), pool, btab, slen]
            # seq 1 keeps the decode step's own trace (no size-1 axes)
            with scope("paged_read"):
                ctx, pool = self._attend_paged(q_nope[:, 0], q_rope[:, 0],
                                               latent[:, 0], wkv_b, pool,
                                               btab, slen)
            with scope("out"):
                out = jnp.einsum("bhd,hde->be", ctx, wo)[:, None]
                return [out.astype(x.dtype), pool, btab, slen]
        # expanded: keys and values out of the latent, causal attention
        # over the step's own tokens
        with scope("core"):
            c, k_rope = latent[..., :rk], latent[..., rk:]
            kvh = jnp.einsum("bsc,chd->bshd", c, wkv_b)
            if self.core_plan() == "flash":
                from .pallas.flash_attention import flash_mha

                keys = jnp.concatenate(
                    [kvh[..., :dn],
                     jnp.broadcast_to(k_rope[:, :, None], q_rope.shape)],
                    axis=-1)
                ctx = flash_mha(jnp.concatenate([q_nope, q_rope], axis=-1),
                                keys, kvh[..., dn:], softmax_scale(p), True)
            else:
                ctx = self._attend_dense(q_nope, q_rope, kvh, k_rope)
        with scope("out"):
            return [jnp.einsum("bqhd,hde->bqe", ctx, wo).astype(x.dtype)]

    def core_plan(self) -> str:
        """"flash" or "dense": the stateless path's causal core for the
        op's declared sequence length, `MultiHeadAttention`'s rule
        (`flash_min_seq`, set on every op at compile)."""
        from ..config import DEFAULT_FLASH_MIN_SEQ

        s = self.inputs[0].shape.logical_shape[1]
        flash_min = getattr(self, "_flash_min_seq", DEFAULT_FLASH_MIN_SEQ)
        return "flash" if s >= flash_min else "dense"

    def _attend_dense(self, q_nope, q_rope, kvh, k_rope):
        """einsum, softmax, einsum with the `[b, h, s, s]` scores in
        HBM: short rows."""
        p: MLAParams = self.params
        dn = p.qk_nope_head_dim
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, kvh[..., :dn],
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope,
                               preferred_element_type=jnp.float32))
        s = q_nope.shape[1]
        keep = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(keep, scores * softmax_scale(p),
                           jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(scores, axis=-1).astype(q_nope.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, kvh[..., dn:])

    def _attend_paged(self, q_nope, q_rope, latent, wkv_b, pool, btab,
                      slen):
        """One token a row through the paged latent cache: write the
        token's latent at the row's own position (idle slots point at
        scratch block 0), then attend over the row's gathered blocks
        with `W_kvb` absorbed.  Gathered slots past a row's length hold
        other sequences' bytes; the per-row position mask takes them
        out of the softmax exactly."""
        p: MLAParams = self.params
        dn, rk = p.qk_nope_head_dim, p.kv_lora_rank
        b, page = latent.shape[0], self._kv_page_size
        pos = slen.reshape(b).astype(jnp.int32)
        blk = jnp.take_along_axis(btab, (pos // page)[:, None], axis=1)[:, 0]
        pool = pool.at[blk, pos % page].set(latent.astype(pool.dtype))
        n = btab.shape[1] * page
        view = jnp.take(pool, btab, axis=0).reshape(b, n, -1) \
            .astype(q_nope.dtype)
        q_lat = jnp.einsum("bhd,chd->bhc", q_nope, wkv_b[..., :dn])
        scores = jnp.einsum(
            "bhc,bnc->bhn", jnp.concatenate([q_lat, q_rope], axis=-1), view,
            preferred_element_type=jnp.float32) * softmax_scale(p)
        live = jnp.arange(n, dtype=jnp.int32)[None, :] <= pos[:, None]
        scores = jnp.where(live[:, None, :], scores,
                           jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(scores, axis=-1).astype(q_nope.dtype)
        out_lat = jnp.einsum("bhn,bnc->bhc", probs, view[..., :rk])
        return jnp.einsum("bhc,chd->bhd", out_lat, wkv_b[..., dn:]), pool

    def _attend_paged_chunk(self, q_nope, q_rope, latent, wkv_b, pool,
                            btab, slen):
        """s > 1 tokens a row through the paged latent cache in one
        pass: row i's token j is written at `slen[i] + j`, ALL s before
        the read, the row's view is gathered once, and query j attends
        `key_pos <= slen[i] + j`.  Writing the whole chunk first equals
        write-then-attend a token at a time: a later chunk position
        lands on a key the earlier queries' masks exclude (the argument
        `MultiHeadAttention._attend_decode_paged_kernel` makes).  Same
        precision and formulation as `_attend_paged`.

        The pad contract of a chunked prefill is kept here, explicitly:
        a position `>= max_seq` (a near-full row's trailing pads) is
        written to scratch block 0, as through a zeroed table row, at a
        position clamped in range, whatever jax's out-of-range gather
        and scatter modes are; a rider (all-zero table row, `slen` 0)
        writes scratch only.  Scratch writes collide; no query that
        matters reads them."""
        p: MLAParams = self.params
        dn, rk = p.qk_nope_head_dim, p.kv_lora_rank
        b, s = latent.shape[:2]
        page = self._kv_page_size
        n = btab.shape[1] * page
        pos = (slen.reshape(b, 1).astype(jnp.int32)
               + jnp.arange(s, dtype=jnp.int32))  # [b, s]
        at = jnp.minimum(pos, n - 1)
        blk = jnp.where(pos < n,
                        jnp.take_along_axis(btab, at // page, axis=1), 0)
        pool = pool.at[blk, at % page].set(latent.astype(pool.dtype))
        view = jnp.take(pool, btab, axis=0).reshape(b, n, -1) \
            .astype(q_nope.dtype)
        q_lat = jnp.einsum("bshd,chd->bshc", q_nope, wkv_b[..., :dn])
        scores = jnp.einsum(
            "bshc,bnc->bhsn", jnp.concatenate([q_lat, q_rope], axis=-1),
            view, preferred_element_type=jnp.float32) * softmax_scale(p)
        live = jnp.arange(n, dtype=jnp.int32)[None, None, :] \
            <= pos[:, :, None]  # [b, s, n]
        scores = jnp.where(live[:, None], scores,
                           jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(scores, axis=-1).astype(q_nope.dtype)
        out_lat = jnp.einsum("bhsn,bnc->bshc", probs, view[..., :rk])
        return jnp.einsum("bshc,chd->bshd", out_lat, wkv_b[..., dn:]), pool

    def flops(self):
        p: MLAParams = self.params
        b, s, e = self.inputs[0].shape.logical_shape
        h = p.num_heads
        dq = p.qk_nope_head_dim + p.qk_rope_head_dim
        query = (e * p.q_lora_rank + p.q_lora_rank * h * dq
                 if p.q_lora_rank else e * h * dq)
        proj = 2.0 * b * s * (
            query + e * p.latent_width + h * p.v_head_dim * e)
        if self._paged():
            # absorbed: the query into the latent and the values out of
            # it, scores and the weighted sum over the gathered view
            n = self._decode_max_seq
            return proj + 2.0 * b * s * h * (
                p.kv_lora_rank * (p.qk_nope_head_dim + p.v_head_dim)
                + n * (p.latent_width + p.kv_lora_rank))
        expand = 2.0 * b * s * p.kv_lora_rank * h * (
            p.qk_nope_head_dim + p.v_head_dim)
        return proj + expand + 2.0 * b * h * s * s * (dq + p.v_head_dim)
