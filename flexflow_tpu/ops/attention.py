"""Multi-head attention.

Reference: src/ops/attention.cc (926 LoC) + attention.cu — one monolithic
cudnnMultiHeadAttnForward call (attention.cu:35) with packed qkv/out
weights; head-partition parallelism comes from the
create_partition_attention_combine / create_replicate_attention_reduce
substitutions (substitution.cc:1762-1770).

TPU-first re-design: explicit per-projection weights shaped
[embed, heads, head_dim] so the **heads dim is a first-class shardable
dim** (ShardConfig.channel = head degree, the TP axis); the score/value
matmuls are dot_generals on the MXU in bf16; output-projection
contraction over heads yields a partial-sum output (replica degree =
head degree) exactly like the reference's Reduction-consumed attention
output.  Sequence parallelism for long context is handled by ring
attention over the mesh's "seq" axis (flexflow_tpu/parallel/
ring_attention.py) — a capability the reference lacks (SURVEY §5).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..fftype import DataType, OperatorType
from ..initializer import DEFAULT_WEIGHT_INIT, GlorotUniform
from ..obs.scopes import scope
from ..tensor import ParallelDim, ParallelTensorShape
from .op import DispatchGroup, Op, ShapeError, WeightSpec


# force the flash kernel when the per-device [b, h, q, k] score tensor
# would exceed this, regardless of flash_min_seq — OOM insurance for the
# non-flash branch, which counts on XLA fusing the scores away
_FLASH_FORCE_SCORE_BYTES = 2 << 30


@dataclasses.dataclass(frozen=True)
class MultiHeadAttentionParams:
    embed_dim: int
    num_heads: int
    kdim: int = 0  # 0 -> embed_dim
    vdim: int = 0
    dropout: float = 0.0
    use_bias: bool = False
    add_bias_kv: bool = False
    add_zero_attn: bool = False
    causal: bool = False
    # Everything below is off by default (GPT and BERT build none of it).
    # Grouped-query heads: `num_kv_heads` key/value heads (0 -> num_heads),
    # each shared by num_heads / num_kv_heads query heads; the caches
    # and the paged pool hold the key/value heads only.
    num_kv_heads: int = 0
    # RMS norm of every query and key head over its channels, one gain
    # per channel (`q_norm`, `k_norm`), before the rotary embedding
    qk_norm: bool = False
    norm_eps: float = 1e-6
    norm_zero_centered: bool = False  # gains applied as 1 + g
    # rotary embedding on the first `rotary_dim` channels of every query
    # and key head (first half rotated against second half), positions
    # from the op's own mode: 0..s-1 with no cache, the cache position
    # on, or each row's `seq_lens` on
    rotary_dim: int = 0
    rope_theta: float = 10000.0
    # `wq` also projects a gate per head ([q | gate], k_channels +
    # v_channels wide): out = wo (attn * sigmoid(gate))
    output_gate: bool = False
    # paged twin, a step of s > 1 tokens: write the chunk, gather the
    # row's view ONCE and attend with a [s, n] mask (what a family
    # that carries `prefill_pass` builds), instead of once a position
    # at the decode step's shapes (GPT's byte equality)
    paged_read_once: bool = False
    # the paged pools are `[blocks, kv_heads, page, d]`, a head's rows of
    # a page side by side, not `[blocks, page, kv_heads, d]`: what the
    # in-place read of GROUPED heads folds a head at a time
    # (ops/pallas/paged_attention.py `_fold_head_major`; needs
    # `paged_read_once`)
    kv_head_major: bool = False
    # a causal query at position p sees keys `p - sliding_window < j <=
    # p` (its own included); 0 = every key before it.  A decode twin of
    # such a layer keeps a bounded ring a slot instead of pages
    # (`MultiHeadAttention` docstring)
    sliding_window: int = 0
    # ONE gate a head from the layer's input, weight `wg [embed, heads]`:
    # out = wo (attn * sigmoid(x wg)[..., None])  (`output_gate` is the
    # gate a channel that `wq` projects)
    head_gate: bool = False
    # YaRN on the rotary channels (ops/rope.py yarn_frequencies; factor 1
    # = plain RoPE); cos and sin times the published `attention_factor`
    rope_factor: float = 1.0
    rope_original_max: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    rope_attention_factor: float = 1.0
    softmax_scale: Optional[float] = None  # None: 1 / sqrt(head channels)

    @property
    def k_channels(self) -> int:
        return (self.kdim or self.embed_dim) // self.num_heads

    @property
    def v_channels(self) -> int:
        return (self.vdim or self.embed_dim) // self.num_heads

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def group(self) -> int:
        """Query heads a key/value head serves."""
        return self.num_heads // self.kv_heads


def rotate_half(x, positions, rotary_dim: int, theta: float, *,
                freq=None, factor: float = 1.0):
    """Rotary embedding on the first `rotary_dim` channels of
    x [b, s, heads, d] at positions [b, s]: channel i of the first half
    against channel i of the second half, angle `pos * theta^(-2i /
    rotary_dim)`, computed in float32; the other channels pass.
    `freq` [rotary_dim / 2] replaces those frequencies (YaRN's), and
    cos and sin are multiplied by `factor`."""
    half = rotary_dim // 2
    if freq is None:
        freq = theta ** (-np.arange(0, rotary_dim, 2, dtype=np.float64)
                         / rotary_dim)
    angle = (positions.astype(jnp.float32)[..., None, None]
             * jnp.asarray(freq, jnp.float32))  # [b, s, 1, half]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:rotary_dim]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, xf[..., rotary_dim:]],
        axis=-1).astype(x.dtype)


def window_rows_live(window: int, positions, counts) -> int:
    """Ring rows that SOME query of a dispatch sees, ONE window layer's
    worth, from host-owned lengths alone: row i advances over positions
    `positions[i] .. + counts[i] - 1` (numpy, [slots]); its queries see
    the positions in `(positions[i] - window, positions[i] + counts[i])`
    that exist: `min(p + n, window + n - 1)` rows, none where n is 0.
    Never more than the ring holds, so a share of the rows read."""
    pos = np.asarray(positions, np.int64)
    n = np.asarray(counts, np.int64)
    return int((np.minimum(pos + n, window + n - 1) * (n > 0)).sum())


class MultiHeadAttention(Op):
    """A decode twin of a layer with `sliding_window` W keeps no pages:
    its keys and values live in per-slot RINGS `win_k`, `win_v`
    `[slots, kv_heads, R, d]` (`slot_state_entries`; R = `window_ring`
    >= W + the longest step - 1), position p at ring row `p % R`; a
    head's rows lie side by side, as the score product reads them (laid
    out `[slots, R, kv_heads, d]`, the TPU compiler transposes every
    ring on every pass).  A step writes its real tokens' rows first
    (`row_tokens` of them a row; a pad's write is dropped) and then
    query p attends the ring rows whose position, recovered from the
    row's length after the step and the ring index, lies in `(p - W,
    p]`.  Nothing is zeroed at admission: a row the last tenant left
    holds a position the new one's mask excludes."""

    op_type = OperatorType.MULTIHEAD_ATTENTION
    #: the ring is masked by the sequence's own positions
    slot_state_resets = False

    def __init__(self, params, inputs, name="", shard=None,
                 decode_max_seq: int = 0, kv_page_size: int = 0,
                 kv_num_blocks: int = 0, kv_kernel: str = "gather",
                 kv_planes: int = 1, window_ring: int = 0):
        from .op import ShardConfig

        # must exist before Op.__init__ runs make_weight_specs
        self._decode_max_seq = int(decode_max_seq)
        self._window_ring = int(window_ring)
        self._kv_page_size = int(kv_page_size)
        self._kv_num_blocks = int(kv_num_blocks)
        # planes of the paged pool: one a pass of the region that runs
        # this op (FFModel.repeat), in ONE array [planes x num_blocks,
        # page, h, d]; pass t reads and writes block b at row
        # t x num_blocks + b (`loop_state`), so the scatter and both
        # reads address a plane in place
        self.cache_planes = int(kv_planes)
        # paged READ formulation: "gather" materializes the dense
        # [b, N, h, d] view (the bit-identity oracle); "pallas" streams
        # blocks in place through the fused kernel
        # (ops/pallas/paged_attention.py).  The engine decides it and
        # checks Pallas availability BEFORE building the graph
        # (serving/scheduler.py pick_paged_read).
        self._kv_kernel = str(kv_kernel or "gather")
        super().__init__(params, inputs, name=name,
                         shard=shard or ShardConfig())

    def infer_output_shapes(self, input_shapes):
        q, k, v = input_shapes
        p: MultiHeadAttentionParams = self.params
        qd = [d for d in q.dims if not d.is_replica_dim]
        kd = [d for d in k.dims if not d.is_replica_dim]
        vd = [d for d in v.dims if not d.is_replica_dim]
        if len(qd) != 3:
            raise ShapeError(f"{self.name}: expect [batch, seq, embed] inputs")
        if p.num_heads % self.shard.channel != 0:
            raise ShapeError(f"{self.name}: heads {p.num_heads} not divisible by "
                             f"degree {self.shard.channel}")
        if p.num_heads % p.kv_heads or p.kv_heads % self.shard.channel:
            raise ShapeError(
                f"{self.name}: {p.kv_heads} key/value heads must divide "
                f"{p.num_heads} query heads and be divisible by degree "
                f"{self.shard.channel}")
        if p.rotary_dim % 2 or p.rotary_dim > p.k_channels:
            raise ShapeError(
                f"{self.name}: rotary_dim {p.rotary_dim} must be even and "
                f"at most the head's {p.k_channels} channels")
        if p.group > 1:
            if p.add_bias_kv or p.add_zero_attn:
                raise ShapeError(f"{self.name}: kv-append options "
                                 "unsupported with grouped-query heads")
            if self._decode_n() and not self._ring() and not (
                    self._paged() and p.paged_read_once):
                raise ShapeError(
                    f"{self.name}: grouped-query heads are cached in the "
                    "paged pool and read once a step (kv_page_size > 0, "
                    "paged_read_once), by the gather or the Pallas read; "
                    "the dense cache and the per-position read keep one "
                    "head count")
        if p.kv_head_major and self._paged() and not p.paged_read_once:
            raise ShapeError(
                f"{self.name}: a head-major pool (kv_head_major) is read "
                "once a step (paged_read_once)")
        if p.sliding_window:
            if p.sliding_window < 0 or not p.causal or p.add_bias_kv \
                    or p.add_zero_attn:
                raise ShapeError(
                    f"{self.name}: sliding_window {p.sliding_window} needs "
                    "a causal layer without appended keys")
            if qd[1].degree != 1:
                raise ShapeError(
                    f"{self.name}: a sliding window is not built into "
                    "ring attention (sequence sharding)")
            if self._decode_n() and (
                    self._window_ring < p.sliding_window + qd[1].size - 1
                    or self.cache_planes > 1 or qd[0].degree != 1):
                raise ShapeError(
                    f"{self.name}: a window layer's decode twin keeps a "
                    f"ring of window_ring ({self._window_ring}) >= "
                    f"sliding_window {p.sliding_window} + step "
                    f"{qd[1].size} - 1 rows a slot, one plane, slots "
                    "unsharded")
        if qd[1].degree != 1 or kd[1].degree != 1 or vd[1].degree != 1:
            # Seq partitioning lowers to ring attention — legal only
            # when q/k/v share one seq sharding (self-attention SP).
            # (Runtime dispatch keys on q's seq degree, so q-only
            # sharding must be validated here too.)
            if not (qd[1].degree == kd[1].degree == vd[1].degree):
                raise ShapeError(
                    f"{self.name}: ring attention needs equal q/k/v seq "
                    f"degrees, got {qd[1].degree}/{kd[1].degree}/{vd[1].degree}"
                )
            if self.params.add_bias_kv or self.params.add_zero_attn:
                raise ShapeError(
                    f"{self.name}: kv-append options unsupported with "
                    f"sequence sharding"
                )
            if self.params.dropout > 0.0:
                raise ShapeError(
                    f"{self.name}: attention dropout unsupported with "
                    f"sequence sharding (ring attention)"
                )
        ri = q.replica_degree
        c = self.shard.channel
        if c > 1 and ri % c == 0:
            ri //= c
        dims = (
            ParallelDim(qd[0].size, qd[0].degree),
            ParallelDim(qd[1].size, qd[1].degree),
            ParallelDim(p.embed_dim, 1),
            ParallelDim(1, ri * c, is_replica_dim=True),  # head-contraction partials
        )
        return [ParallelTensorShape(dims, q.dtype)]

    # -- KV-cache decode mode -------------------------------------------
    # Set op._decode_max_seq = N (before compile) to run this attention
    # as an incremental decoder: per-step q/k/v of seq length 1, k/v
    # appended into fixed-shape [b, N, h, d] cache state carried through
    # the op-state pytree (the BatchNorm running-stats convention), so
    # generation is O(T) instead of re-running the full forward per
    # token.  The reference has no incremental decoding at all (its
    # legacy nmt/ re-runs the graph; triton/ is an incomplete
    # prototype) — this is TPU-native serving machinery.
    def _decode_n(self) -> int:
        return int(getattr(self, "_decode_max_seq", 0) or 0)

    # Paged decode mode (serving/kv_pool.py, the vLLM PagedAttention
    # design, SOSP'23): instead of one dense [b, N, h, d] cache per
    # sequence slot, k/v live in a POOL of fixed-size blocks
    # [num_blocks, page, h, d] shared by all slots; a per-slot block
    # table [b, N/page] maps logical block -> physical block and a
    # per-slot seq_lens [b] carries each row's own position (continuous
    # batching runs rows at different positions in one step).  The
    # block table and seq_lens are HOST-owned (the scheduler allocates
    # on extend / frees on retire and rewrites them between steps);
    # in-graph they are read-only and returned unchanged.
    def _paged(self) -> bool:
        return self._decode_n() > 0 and not self._ring() and \
            int(getattr(self, "_kv_page_size", 0) or 0) > 0

    def _ring(self) -> bool:
        """A window layer's decode twin: rings a slot, no pages."""
        return self._decode_n() > 0 and self.params.sliding_window > 0

    def cache_entries(self):
        if self._ring():
            return ()
        return ("k_cache", "v_cache") if self._decode_n() else ()

    def slot_state_entries(self):
        return ("win_k", "win_v") if self._ring() else ()

    def ctor_kwargs(self) -> dict:
        n = self._decode_n()
        if not n:
            return {}
        kw = {"decode_max_seq": n}
        if self._ring():
            return dict(kw, window_ring=self._window_ring)
        if self.cache_planes > 1:
            kw["kv_planes"] = self.cache_planes
        if self._paged():
            kw["kv_page_size"] = self._kv_page_size
            kw["kv_num_blocks"] = self._kv_num_blocks
            if getattr(self, "_kv_kernel", "gather") != "gather":
                kw["kv_kernel"] = self._kv_kernel
        return kw

    def loop_state(self, entries, step):
        if not self._paged() or self.cache_planes == 1:
            return entries
        # pass `step`'s plane: block 0 of every plane is its scratch
        return dict(entries, block_table=entries["block_table"]
                    + step * jnp.int32(self._kv_num_blocks))

    def num_trainable_weights(self) -> int:
        n = 4
        p: MultiHeadAttentionParams = self.params
        if p.use_bias:
            n += 4
        if p.add_bias_kv:
            n += 2
        if p.qk_norm:
            n += 2
        if p.head_gate:
            n += 1
        return n

    def make_weight_specs(self, input_shapes):
        q, k, v = input_shapes
        p: MultiHeadAttentionParams = self.params
        qd = [d for d in q.dims if not d.is_replica_dim]
        batch_degree = qd[0].degree * qd[1].degree
        c = self.shard.channel
        dt = q.dtype

        def w(shape_sizes, head_axis):
            dims = []
            for i, s in enumerate(shape_sizes):
                dims.append(ParallelDim(s, c if i == head_axis else 1))
            extra = batch_degree if head_axis is not None else batch_degree * c
            dims.append(ParallelDim(1, extra, is_replica_dim=True))
            return ParallelTensorShape(tuple(dims), dt)

        embed = p.embed_dim
        init = GlorotUniform(fan_in=embed, fan_out=embed)
        q_width = p.k_channels + (p.v_channels if p.output_gate else 0)
        specs = [
            WeightSpec("wq", w((embed, p.num_heads, q_width), 1), init),
            WeightSpec("wk", w((k.logical_shape[-1], p.kv_heads, p.k_channels), 1), init),
            WeightSpec("wv", w((v.logical_shape[-1], p.kv_heads, p.v_channels), 1), init),
            WeightSpec("wo", w((p.num_heads, p.v_channels, embed), 0), init),
        ]
        from ..initializer import ZeroInitializer

        zero = ZeroInitializer()
        if p.use_bias:
            specs += [
                WeightSpec("bq", w((p.num_heads, p.k_channels), 0), zero),
                WeightSpec("bk", w((p.num_heads, p.k_channels), 0), zero),
                WeightSpec("bv", w((p.num_heads, p.v_channels), 0), zero),
                WeightSpec("bo", w((embed,), None), zero),
            ]
        if p.add_bias_kv:
            # one learnable bias token appended to the k/v sequences
            specs += [
                WeightSpec("bias_k", w((1, p.num_heads, p.k_channels), 1), init),
                WeightSpec("bias_v", w((1, p.num_heads, p.v_channels), 1), init),
            ]
        if p.qk_norm:
            from ..initializer import ConstantInitializer

            ident = ConstantInitializer(0.0 if p.norm_zero_centered else 1.0)
            specs += [
                WeightSpec("q_norm", w((p.k_channels,), None), ident),
                WeightSpec("k_norm", w((p.k_channels,), None), ident),
            ]
        if p.head_gate:
            specs.append(WeightSpec("wg", w((embed, p.num_heads), 1), init))
        n = self._decode_n()
        if n > 0:
            if p.add_bias_kv or p.add_zero_attn:
                raise ShapeError(
                    f"{self.name}: kv-append options unsupported in "
                    "decode mode"
                )
            if qd[1].degree != 1:
                raise ShapeError(
                    f"{self.name}: decode mode needs an unsharded seq dim"
                )

            if self._ring():
                return specs + self._ring_state_specs(qd, dt)
            if self._paged():
                return specs + self._paged_state_specs(qd, dt)
            if self.cache_planes > 1:
                raise ShapeError(
                    f"{self.name}: the dense per-slot cache holds one "
                    "plane; inside a repeated region the cache is the "
                    "paged pool (kv_page_size > 0)")

            def cache(d_head):
                dims = (
                    ParallelDim(qd[0].size, qd[0].degree),
                    ParallelDim(n),
                    ParallelDim(p.kv_heads, c),
                    ParallelDim(d_head),
                    ParallelDim(1, q.replica_degree, is_replica_dim=True),
                )
                return ParallelTensorShape(dims, dt)

            pos_shape = ParallelTensorShape(
                (ParallelDim(1),
                 ParallelDim(1, q.total_degree, is_replica_dim=True)),
                DataType.INT32,
            )
            specs += [
                WeightSpec("k_cache", cache(p.k_channels), zero),
                WeightSpec("v_cache", cache(p.v_channels), zero),
                WeightSpec("cache_pos", pos_shape, zero),
            ]
        return specs

    def _ring_state_specs(self, qd, dt):
        """State specs of a window layer's decode twin: the two rings
        and the host-owned lengths (`seq_lens`, `row_tokens`)."""
        from ..initializer import ZeroInitializer

        p: MultiHeadAttentionParams = self.params
        zero = ZeroInitializer()
        one = ParallelDim(1, 1, is_replica_dim=True)

        def ring(d_head):
            return ParallelTensorShape(
                (ParallelDim(qd[0].size),
                 ParallelDim(p.kv_heads, self.shard.channel),
                 ParallelDim(self._window_ring), ParallelDim(d_head), one),
                dt)

        ints = ParallelTensorShape((ParallelDim(qd[0].size), one),
                                   DataType.INT32)
        return [
            WeightSpec("win_k", ring(p.k_channels), zero),
            WeightSpec("win_v", ring(p.v_channels), zero),
            WeightSpec("seq_lens", ints, zero),
            WeightSpec("row_tokens", ints, zero),
        ]

    def _paged_state_specs(self, qd, dt):
        """State specs for paged decode: block-pool k/v caches plus the
        host-owned per-slot block table and sequence lengths."""
        from ..initializer import ZeroInitializer

        p: MultiHeadAttentionParams = self.params
        n, page, nb = self._decode_n(), self._kv_page_size, \
            self._kv_num_blocks
        if not 1 <= qd[1].size <= n:
            # seq length C > 1 is the CHUNKED-PREFILL twin
            # (decoding.build_paged_chunk_step): C tokens scattered at
            # each row's own positions per step, causal within the
            # chunk.  seq 1 remains the decode twin.
            raise ShapeError(
                f"{self.name}: paged decode chunk must be within [1, "
                f"decode_max_seq={n}], got {qd[1].size}"
            )
        if qd[0].degree != 1:
            # head (channel) sharding IS supported — the pool shards
            # its head dim over the 'model' axis below, the block
            # scatter/gather index only the block/page dims, and the
            # Pallas dispatch shard_maps over heads.  Batch sharding is
            # not: slots are scheduler-owned host state, and splitting
            # them would split the block table.
            raise ShapeError(
                f"{self.name}: paged decode mode needs an unsharded "
                "batch dim (slots are host-owned; use head "
                "tensor-parallelism via ShardConfig.channel instead)"
            )
        if page < 1 or n % page:
            raise ShapeError(
                f"{self.name}: kv_page_size {page} must divide "
                f"decode_max_seq {n} (the gathered view must equal the "
                "dense cache shape for bit-identical attention)"
            )
        if nb < 2:
            raise ShapeError(
                f"{self.name}: kv_num_blocks {nb} < 2 (block 0 is the "
                "scratch block idle slots write into)"
            )
        zero = ZeroInitializer()

        def pool(d_head):
            # head dim carries the channel (tp) degree: the pool shards
            # [nb, page, h/tp, d] per chip — per-chip KV bytes are 1/tp
            # — while the block scatter/gather address only the
            # unsharded block/page dims, so the host-owned block
            # table / COW / prefix-sharing plumbing never sees the
            # sharding.
            heads = ParallelDim(p.kv_heads, self.shard.channel)
            dims = (
                ParallelDim(nb * self.cache_planes),
                *((heads, ParallelDim(page)) if p.kv_head_major
                  else (ParallelDim(page), heads)),
                ParallelDim(d_head),
                ParallelDim(1, 1, is_replica_dim=True),
            )
            return ParallelTensorShape(dims, dt)

        def ints(*sizes):
            dims = tuple(ParallelDim(s) for s in sizes) + (
                ParallelDim(1, 1, is_replica_dim=True),)
            return ParallelTensorShape(dims, DataType.INT32)

        return [
            WeightSpec("k_cache", pool(p.k_channels), zero),
            WeightSpec("v_cache", pool(p.v_channels), zero),
            WeightSpec("block_table", ints(qd[0].size, n // page), zero),
            WeightSpec("seq_lens", ints(qd[0].size), zero),
        ]

    def forward(self, inputs, weights, *, training=False, rng=None):
        q, k, v = inputs
        p: MultiHeadAttentionParams = self.params
        wo = weights[3]
        with scope("proj"):
            qh, kh, vh, gate, bo = self._project(q, k, v, weights)
        scale = p.softmax_scale or 1.0 / np.sqrt(p.k_channels)

        def out_of(ctx):
            with scope("out"):
                if gate is not None:
                    ctx = ctx * jax.nn.sigmoid(gate).astype(ctx.dtype)
                if p.head_gate:  # `wg`: the last trainable weight
                    wg = weights[self.num_trainable_weights() - 1]
                    ctx = ctx * jax.nn.sigmoid(jnp.einsum(
                        "bse,eh->bsh", q, wg)).astype(ctx.dtype)[..., None]
                out = jnp.einsum("bqhd,hde->bqe", ctx, wo)
                if bo is not None:
                    out = out + bo[None, None]
                return out.astype(q.dtype)

        if self._ring():
            win_k, win_v, slen, row_tokens = weights[-4:]
            with scope("window_read"):
                ctx, win_k, win_v = self._attend_ring(
                    qh, kh, vh, win_k, win_v, slen, row_tokens, scale)
            return [out_of(ctx), win_k, win_v, slen, row_tokens]
        if self._paged():
            k_cache, v_cache, btab, slen = weights[-4:]
            attend = (self._attend_decode_paged_once if p.paged_read_once
                      else self._attend_decode_paged)
            with scope("paged_read"):
                ctx, k_cache, v_cache = attend(
                    qh, kh, vh, k_cache, v_cache, btab, slen, scale
                )
            return [out_of(ctx), k_cache, v_cache, btab, slen]
        if self._decode_n() > 0:
            k_cache, v_cache, pos = weights[-3], weights[-2], weights[-1]
            with scope("core"):
                ctx, k_cache, v_cache, pos = self._attend_decode(
                    qh, kh, vh, k_cache, v_cache, pos, scale
                )
            return [out_of(ctx), k_cache, v_cache, pos]
        with scope("core"):
            if p.group > 1:
                # no cache: every query head gets its key/value head's copy
                kh = jnp.repeat(kh, p.group, axis=2)
                vh = jnp.repeat(vh, p.group, axis=2)
            ctx = self._attend(qh, kh, vh, scale, training=training, rng=rng)
        return [out_of(ctx)]

    def _project(self, q, k, v, weights):
        """(qh, kh, vh, gate or None, output bias or None): the three
        projections with what is applied to them before the core (biases,
        appended keys, the output gate's half of q, head norms, rotary)."""
        p: MultiHeadAttentionParams = self.params
        wq, wk, wv = weights[:3]
        wi = 4
        # [b, s, e] x [e, h, d] -> [b, s, h, d]
        qh = jnp.einsum("bse,ehd->bshd", q, wq)
        kh = jnp.einsum("bse,ehd->bshd", k, wk)
        vh = jnp.einsum("bse,ehd->bshd", v, wv)
        bo = None
        if p.use_bias:
            bq, bk, bv, bo = weights[wi : wi + 4]
            wi += 4
            qh = qh + bq[None, None]
            kh = kh + bk[None, None]
            vh = vh + bv[None, None]
        if p.add_bias_kv:
            bias_k, bias_v = weights[wi : wi + 2]
            wi += 2
            bsz = kh.shape[0]
            kh = jnp.concatenate([kh, jnp.broadcast_to(bias_k[None], (bsz,) + bias_k.shape)], axis=1)
            vh = jnp.concatenate([vh, jnp.broadcast_to(bias_v[None], (bsz,) + bias_v.shape)], axis=1)
        if p.add_zero_attn:
            bsz, _, h, dk = kh.shape
            dv = vh.shape[-1]
            kh = jnp.concatenate([kh, jnp.zeros((bsz, 1, h, dk), kh.dtype)], axis=1)
            vh = jnp.concatenate([vh, jnp.zeros((bsz, 1, h, dv), vh.dtype)], axis=1)
        gate = None
        if p.output_gate:
            qh, gate = qh[..., :p.k_channels], qh[..., p.k_channels:]
        if p.qk_norm:
            from .norm import rms_normalize

            q_norm, k_norm = weights[wi : wi + 2]
            qh = rms_normalize(qh, q_norm, p.norm_eps, p.norm_zero_centered)
            kh = rms_normalize(kh, k_norm, p.norm_eps, p.norm_zero_centered)
        if p.rotary_dim:
            positions = self._positions(q.shape[0], q.shape[1], weights)
            yarn = {}
            if p.rope_factor > 1 or p.rope_attention_factor != 1.0:
                from .rope import yarn_frequencies

                yarn = dict(
                    freq=yarn_frequencies(
                        p.rotary_dim, p.rope_theta, p.rope_factor,
                        p.rope_original_max, p.beta_fast, p.beta_slow),
                    factor=float(p.rope_attention_factor))
            qh = rotate_half(qh, positions, p.rotary_dim, p.rope_theta,
                             **yarn)
            kh = rotate_half(kh, positions, p.rotary_dim, p.rope_theta,
                             **yarn)
        return qh, kh, vh, gate, bo

    def _positions(self, b, s, weights):
        """[b, s] positions of the step's tokens, for the rotary
        embedding: each row's `seq_lens` on (paged), the cache position
        on (dense cache), 0..s-1 (no cache)."""
        steps = jnp.arange(s, dtype=jnp.int32)[None, :]
        if self._ring():
            return weights[-2].reshape(b, 1).astype(jnp.int32) + steps
        if self._paged():
            return weights[-1].reshape(b, 1).astype(jnp.int32) + steps
        if self._decode_n() > 0:
            return jnp.broadcast_to(
                weights[-1].reshape(1, 1).astype(jnp.int32) + steps, (b, s))
        return jnp.broadcast_to(steps, (b, s))

    def _attend_decode(self, qh, kh, vh, k_cache, v_cache, pos, scale):
        """Incremental attention: append this step's k/v at position
        `pos` (a [1] int32 carried in op state), attend the new queries
        over the cache prefix.  q/k/v seq length is the step size
        (usually 1); causality across steps comes from masking cache
        positions beyond pos, within-step causality from the usual
        triangular mask."""
        p: MultiHeadAttentionParams = self.params
        s = qh.shape[1]
        pos0 = pos.reshape(())  # scalar current length
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, kh.astype(k_cache.dtype), (0, pos0, 0, 0)
        )
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, vh.astype(v_cache.dtype), (0, pos0, 0, 0)
        )
        n = k_cache.shape[1]
        key_pos = jnp.arange(n, dtype=jnp.int32)  # absolute cache slots
        q_pos = pos0 + jnp.arange(s, dtype=jnp.int32)
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", qh, k_cache.astype(qh.dtype)
        ) * scale
        mask = key_pos[None, :] <= q_pos[:, None]  # [s, n]
        if not p.causal:
            # bidirectional within the visible prefix (encoder-style
            # caches): every written slot is attendable
            mask = jnp.broadcast_to(
                key_pos[None, :] < pos0 + s, (s, n)
            )
        scores = jnp.where(
            mask[None, None], scores, jnp.finfo(scores.dtype).min
        )
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v_cache.astype(qh.dtype))
        return ctx, k_cache, v_cache, (pos0 + s).reshape(1)

    def _attend_ring(self, qh, kh, vh, win_k, win_v, slen, row_tokens,
                     scale):
        """A window layer's step from each row's own position (class
        docstring): the step's real rows into the ring, then one read
        of the whole ring, masked to each query's window by position."""
        p: MultiHeadAttentionParams = self.params
        b, s = qh.shape[:2]
        window, ring = p.sliding_window, win_k.shape[2]
        start = slen.reshape(b).astype(jnp.int32)
        count = jnp.clip(row_tokens.reshape(b).astype(jnp.int32), 0, s)
        at = start[:, None] + jnp.arange(s, dtype=jnp.int32)  # [b, s]
        rows = jnp.arange(b)[:, None]
        # the row's real tokens only; a pad's row index is dropped
        slot = jnp.where(jnp.arange(s)[None, :] < count[:, None],
                         at % ring, ring)
        if s == 1:
            # (every index but the channels' is a scatter index: with
            # the heads in the update's window the TPU compiler re-lays
            # the whole ring row-major for the scatter and back for the
            # product)
            heads = jnp.arange(p.kv_heads)
            at_row = (rows[:, :, None], heads[None, None, :],
                      slot[:, :, None])
            win_k = win_k.at[at_row].set(kh.astype(win_k.dtype), mode="drop")
            win_v = win_v.at[at_row].set(vh.astype(win_v.dtype), mode="drop")
        else:
            # a chunk's rows by ONE product with a one-hot [b, R, s]
            # (exact: a ring row is 1.0 x the row it takes) and a select:
            # the scatter above takes ~50 ns a row of 128 channels on a
            # v5e, 7 ms a pass of [32, 32] over nine layers (PERF.md,
            # PR 55); a pad's `slot` is R and hits no row
            hit = slot[:, None, :] == jnp.arange(ring)[None, :, None]
            took = jnp.any(hit, axis=-1)[:, None, :, None]  # [b, 1, R, 1]
            exact = (jax.lax.Precision.HIGHEST
                     if kh.dtype == jnp.float32 else None)

            def placed(x, old):
                new = jnp.einsum("brs,bskd->bkrd", hit.astype(x.dtype), x,
                                 precision=exact)
                return jnp.where(took, new.astype(old.dtype), old)

            win_k, win_v = placed(kh, win_k), placed(vh, win_v)
        # ring row r holds the last position under the row's new length
        # that is r mod R (negative: never written by this sequence)
        last = (start + count)[:, None] - 1
        held = last - (last - jnp.arange(ring, dtype=jnp.int32)) % ring
        held = held[:, None, :]  # [b, 1, R] against at [b, s, 1]
        visible = ((held >= 0) & (held <= at[:, :, None])
                   & (held > at[:, :, None] - window))
        qg = qh.reshape(b, s, p.kv_heads, p.group, -1)
        scores = jnp.einsum("bskgd,bkrd->bkgsr", qg, win_k.astype(qh.dtype),
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(visible[:, None, None], scores,
                           jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(scores, axis=-1).astype(qh.dtype)
        ctx = jnp.einsum("bkgsr,bkrd->bskgd", probs, win_v.astype(qh.dtype))
        return ctx.reshape(b, s, p.num_heads, -1), win_k, win_v

    def _attend_decode_paged(self, qh, kh, vh, k_cache, v_cache, btab,
                             slen, scale):
        """Paged incremental attention: write this step's k/v into the
        block pool at each row's OWN position (slot = block_table[i,
        pos_i // page], offset = pos_i % page), then attend over the
        row's gathered block view.  The gather materializes a dense
        [b, N, h, d] view (N = table_len * page == decode_max_seq), so
        the score/softmax/context math is shape-identical to the dense
        `_attend_decode` path — greedy decoding is bit-identical by
        construction, while the RESIDENT cache is the shared pool
        (sum-of-live-lengths HBM instead of b * max_seq).  Gathered
        slots past a row's length hold other sequences' bytes; the
        per-row position mask zeroes them out of the softmax exactly
        (exp underflow of the finfo.min fill), so cross-sequence leaks
        are structurally impossible, not just unlikely.

        A step of s > 1 tokens (the chunked-prefill twin,
        decoding.build_paged_chunk_step) scatters row i's token j at
        position slen[i] + j and attends each chunk token over the
        prefix INCLUDING its own chunk predecessors — the math runs
        per position (scatter j, gather, attend q=1) so every op keeps
        the decode twin's shapes: the per-token k/v bytes match the
        one-token program's wherever XLA lowers same-shape ops
        identically.  (The one-gather/full-matrix formulation is NOT
        rowwise-bitwise-stable — its [s, n] x [n, d] context matmul
        accumulates differently per s — so it is deliberately not
        used.)

        Rows always step the full chunk; idle scheduler slots point
        their table at scratch block 0 with seq_len 0, so their
        (garbage) writes land in scratch and their logits are ignored
        host-side.

        kv_kernel="pallas" keeps the scatter writes (so the POOL bytes
        stay byte-identical to this oracle) but replaces the dense
        gather + attend with one fused kernel dispatch that streams
        each row's own blocks in place
        (ops/pallas/paged_attention.py) — per-step HBM reads scale
        with live tokens instead of decode_max_seq, outputs match this
        path to fp32 tolerance (tests/test_paged_kernel.py)."""
        p: MultiHeadAttentionParams = self.params
        b, s = qh.shape[0], qh.shape[1]
        page = self._kv_page_size
        pos = slen.reshape(b).astype(jnp.int32)  # [b] incoming position
        if getattr(self, "_kv_kernel", "gather") == "pallas":
            return self._attend_decode_paged_kernel(
                qh, kh, vh, k_cache, v_cache, btab, pos, scale)
        n = btab.shape[1] * page
        key_pos = jnp.arange(n, dtype=jnp.int32)
        ctxs = []
        for j in range(s):
            # j == 0 keeps the exact seq-1 trace (no +0 constant node)
            pj = pos if j == 0 else pos + jnp.int32(j)
            blk = jnp.take_along_axis(
                btab, (pj // page)[:, None], axis=1
            )[:, 0]
            off = pj % page
            k_cache = k_cache.at[blk, off].set(
                kh[:, j].astype(k_cache.dtype))
            v_cache = v_cache.at[blk, off].set(
                vh[:, j].astype(v_cache.dtype))
            kv_k = jnp.take(k_cache, btab, axis=0).reshape(
                b, n, p.num_heads, -1)
            kv_v = jnp.take(v_cache, btab, axis=0).reshape(
                b, n, p.num_heads, -1)
            qj = qh if s == 1 else qh[:, j:j + 1]
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk", qj, kv_k.astype(qh.dtype)
            ) * scale
            # one-token attends: causal and visible-prefix masks
            # coincide at key_pos <= pos_i + j (the just-written slot
            # is attendable; later chunk slots are not yet)
            mask = key_pos[None, :] <= pj[:, None]  # [b, n]
            scores = jnp.where(
                mask[:, None, None, :], scores,
                jnp.finfo(scores.dtype).min
            )
            probs = jax.nn.softmax(scores, axis=-1)
            ctxs.append(jnp.einsum(
                "bhqk,bkhd->bqhd", probs, kv_v.astype(qh.dtype)))
        ctx = ctxs[0] if s == 1 else jnp.concatenate(ctxs, axis=1)
        return ctx, k_cache, v_cache

    def _attend_decode_paged_once(self, qh, kh, vh, k_cache, v_cache,
                                  btab, slen, scale):
        """The gather read with ONE view a step, for any step length and
        any query-head group (`paged_read_once`): row i's token j is
        written at `slen[i] + j`, ALL s before the read (a later chunk
        position lands on a key the earlier queries' masks exclude: the
        argument `_attend_decode_paged_kernel` makes), each pool is
        gathered once into `[b, n, kv_heads, d]`, and query j of the
        `group` query heads that share a key/value head attends
        `key_pos <= slen[i] + j`.  The pad contract of a chunked prefill
        is kept explicitly, as `ops/mla.py _attend_paged_chunk` keeps
        it: a position `>= n` is written to scratch block 0 at a
        position clamped in range; a rider (all-zero table row) writes
        scratch only.  Equal to seq-1 stepping by tolerance, not by
        bytes."""
        p: MultiHeadAttentionParams = self.params
        b, s = qh.shape[:2]
        page = self._kv_page_size
        n = btab.shape[1] * page
        pos = (slen.reshape(b, 1).astype(jnp.int32)
               + jnp.arange(s, dtype=jnp.int32))  # [b, s]
        at = jnp.minimum(pos, n - 1)
        blk = jnp.where(pos < n,
                        jnp.take_along_axis(btab, at // page, axis=1), 0)
        if p.kv_head_major:
            # (every index but the channels' a scatter index, as the
            # rings': `_attend_ring`)
            where = (blk[:, :, None], jnp.arange(p.kv_heads)[None, None, :],
                     (at % page)[:, :, None])
        else:
            where = (blk, at % page)
        k_cache = k_cache.at[where].set(kh.astype(k_cache.dtype))
        v_cache = v_cache.at[where].set(vh.astype(v_cache.dtype))
        if self._kv_kernel == "pallas":
            # the same writes, then the row's live pages read in place
            # (the kernel takes the query heads grouped as they are)
            ctx = self._paged_kernel_read(
                qh, k_cache, v_cache, btab,
                slen.reshape(b).astype(jnp.int32), scale)
            return ctx, k_cache, v_cache

        def view(pool):  # [b, n, kv_heads, d] of the row's table
            got = jnp.take(pool, btab, axis=0)
            if p.kv_head_major:  # [b, tw, h, page, d]
                got = got.transpose(0, 1, 3, 2, 4)
            return got.reshape(b, n, p.kv_heads, -1).astype(qh.dtype)

        kv_k, kv_v = view(k_cache), view(v_cache)
        qg = qh.reshape(b, s, p.kv_heads, p.group, -1)
        scores = jnp.einsum("bskgd,bnkd->bkgsn", qg, kv_k,
                            preferred_element_type=jnp.float32) * scale
        live = jnp.arange(n, dtype=jnp.int32)[None, None, :] \
            <= pos[:, :, None]  # [b, s, n]
        scores = jnp.where(live[:, None, None], scores,
                           jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(scores, axis=-1).astype(qh.dtype)
        ctx = jnp.einsum("bkgsn,bnkd->bskgd", probs, kv_v)
        return ctx.reshape(b, s, p.num_heads, -1), k_cache, v_cache

    def _attend_decode_paged_kernel(self, qh, kh, vh, k_cache, v_cache,
                                    btab, pos, scale):
        """Fused-kernel paged attention: scatter this step's k/v at
        each row's own positions (the SAME writes, in the same order,
        as the gather oracle — pool state stays byte-identical between
        formulations), then one paged_attention dispatch reads each
        row's blocks in place.  Scattering the whole chunk before
        attending is equivalent to the oracle's interleaved loop: a
        later chunk position's write lands at a key position the
        earlier queries' masks exclude."""
        s, page = qh.shape[1], self._kv_page_size
        n = btab.shape[1] * page
        for j in range(s):
            pj = pos if j == 0 else pos + jnp.int32(j)
            if j > 0:
                # a chunk's trailing PAD positions can run past the
                # position table; route those writes to scratch (zeroed
                # table row) and clamp in-range EXPLICITLY — the same
                # guard build_paged_prefill_step carries, because jax's
                # fill-mode OOB-scatter drop is a mode default, not a
                # contract (decoding.py's v18 hardening note)
                safe = (pj < n)[:, None]
                bt_j = jnp.where(safe, btab, 0)
                pj = jnp.minimum(pj, n - 1)
            else:
                bt_j = btab  # decode positions are in-range by contract
            blk = jnp.take_along_axis(
                bt_j, (pj // page)[:, None], axis=1
            )[:, 0]
            off = pj % page
            k_cache = k_cache.at[blk, off].set(
                kh[:, j].astype(k_cache.dtype))
            v_cache = v_cache.at[blk, off].set(
                vh[:, j].astype(v_cache.dtype))
        ctx = self._paged_kernel_read(qh, k_cache, v_cache, btab, pos,
                                      scale)
        return ctx, k_cache, v_cache

    def _paged_kernel_read(self, qh, k_cache, v_cache, btab, pos, scale):
        """One `paged_attention` dispatch over pools already written."""
        from .pallas.paged_attention import paged_attention

        major = bool(getattr(self.params, "kv_head_major", False))
        mesh = getattr(self, "_mesh", None)
        if self.shard.channel > 1 and mesh is not None \
                and mesh.devices.size > 1:
            # GSPMD cannot partition a pallas_call: shard the kernel
            # grid over the head axis explicitly (the _flash_sharded
            # pattern).  Per shard the kernel sees [b, s, h/tp, d]
            # queries against the local [nb, page, h/tp, d] pool slice;
            # the block table and positions are replicated host state.
            # No TPU gate — CPU meshes run the kernel in interpret mode
            # so tests exercise this exact dispatch.
            from jax.sharding import PartitionSpec

            batch_spec, _, head_spec = self._view_specs()
            qspec = PartitionSpec(batch_spec, None, head_spec, None)
            pool_spec = (PartitionSpec(None, head_spec, None, None) if major
                         else PartitionSpec(None, None, head_spec, None))
            ctx = jax.shard_map(
                lambda q_, k_, v_, bt_, ps_: paged_attention(
                    q_, k_, v_, bt_, ps_, scale, head_major=major),
                mesh=mesh,
                in_specs=(qspec, pool_spec, pool_spec,
                          PartitionSpec(None, None), PartitionSpec(None)),
                out_specs=qspec,
                check_vma=False,
            )(qh, k_cache, v_cache, btab, pos)
        else:
            ctx = paged_attention(qh, k_cache, v_cache, btab, pos, scale,
                                  head_major=major)
        return ctx

    # -- attention core dispatch ----------------------------------------
    def _seq_degree(self) -> int:
        qdims = [d for d in self.inputs[0].shape.dims if not d.is_replica_dim]
        return qdims[1].degree

    def _view_specs(self):
        """(batch_spec, seq_axes, head_spec) from the compiled machine
        views — shared by the ring and flash shard_map paths."""
        view = self.inputs[0].machine_view
        qdims_axes = [
            a for d, a in zip(self.inputs[0].shape.dims, view.axes)
            if not d.is_replica_dim
        ] if view is not None else [(), (), ()]
        head_view = self.weights[0].machine_view
        head_axes = head_view.axes[1] if head_view is not None else ()

        def spec_of(axes):
            if not axes:
                return None
            return axes[0] if len(axes) == 1 else tuple(axes)

        return spec_of(qdims_axes[0]), qdims_axes[1], spec_of(head_axes)

    def _core_plan(self, b, sq, sk, h, d, itemsize, use_dropout) -> str:
        """Which attention core these (global) shapes take: "ring",
        "one_tile" / "online" (a Pallas kernel, `pick_tiling`), "jnp"
        (the flash branch's twin, off-TPU) or "dense" (einsum ->
        softmax -> einsum with the [b, h, q, k] scores in HBM).  The
        one decision `_attend` acts on and `core_plan` reports."""
        from ..config import DEFAULT_FLASH_MIN_SEQ
        from .pallas.flash_attention import pick_tiling

        p: MultiHeadAttentionParams = self.params
        if self._seq_degree() > 1:
            return "ring"
        kv_appended = sk - self.inputs[1].shape.logical_shape[1]
        # FFConfig.flash_min_seq (--flash-min-seq), set on ops at compile
        flash_min = getattr(self, "_flash_min_seq", DEFAULT_FLASH_MIN_SEQ)
        # HBM guard: when the PER-DEVICE [b, h, q, k] score matrix would
        # be enormous, never trust the non-flash branch's reliance on XLA
        # fusing it away.  Shapes here are global (GSPMD traces the full
        # array), so divide by the partition degrees (batch/seq from the
        # input view, heads from the channel shard).
        # Only the batch and seq partition degrees shrink the [b,h,q,k]
        # score tensor — a hidden-dim partition does not (heads are
        # counted once via shard.channel, replication never shrinks
        # per-device data).
        deg = self.inputs[0].shape.degrees
        data_deg = int(np.prod(deg[:2])) if len(deg) >= 2 else int(deg[0])
        part = max(1, data_deg) * max(1, self.shard.channel)
        scores_bytes = b * h * sq * sk * itemsize // part
        force_flash = scores_bytes > _FLASH_FORCE_SCORE_BYTES
        blocked = (use_dropout or (p.causal and kv_appended)
                   or p.sliding_window > 0)
        if force_flash and blocked:
            import warnings

            warnings.warn(
                f"{self.name}: ~{scores_bytes >> 30} GiB of attention "
                "scores will materialize per device — the flash path "
                "cannot take over because of "
                + ("attention dropout" if use_dropout
                   else "a sliding window (the flash kernel has no "
                        "window in its mask)" if p.sliding_window
                   else "causal attention with appended kv "
                        "(add_bias_kv/add_zero_attn)")
            )
        if blocked or not (sk >= flash_min or force_flash):
            return "dense"
        return pick_tiling(sk, d)

    def core_plan(self, itemsize: int, training: bool = True) -> Optional[str]:
        """`_core_plan` for the op's declared shapes and the step's
        compute itemsize, as a step traced now would decide it (None:
        a decode-mode op never reaches `_attend`).  What
        `build_step_fns` counts."""
        if self._decode_n() > 0:
            return None
        p: MultiHeadAttentionParams = self.params
        b, sq, _ = self.inputs[0].shape.logical_shape
        sk = (self.inputs[1].shape.logical_shape[1]
              + int(p.add_bias_kv) + int(p.add_zero_attn))
        return self._core_plan(b, sq, sk, p.num_heads, p.k_channels,
                               itemsize, training and p.dropout > 0.0)

    def _attend(self, qh, kh, vh, scale, *, training, rng):
        p: MultiHeadAttentionParams = self.params
        use_dropout = training and p.dropout > 0.0 and rng is not None
        b, sq, h, d = qh.shape
        plan = self._core_plan(b, sq, kh.shape[1], h, d,
                               jnp.dtype(qh.dtype).itemsize, use_dropout)
        if plan == "ring":
            # sequence parallelism: ring attention over the seq mesh axis
            from ..parallel.ring_attention import ring_attention

            mesh = getattr(self, "_mesh", None)
            assert mesh is not None and self.inputs[0].machine_view is not None, (
                f"{self.name}: ring attention needs a compiled mesh/view"
            )
            batch_spec, seq_axes, head_spec = self._view_specs()
            assert len(seq_axes) == 1, f"{self.name}: seq dim needs one mesh axis"
            return ring_attention(
                qh, kh, vh, mesh, seq_axes[0],
                batch_spec=batch_spec,
                head_spec=head_spec,
                scale=scale, causal=p.causal,
                training=training,
            )
        if plan != "dense":
            # hot path: flash attention (Pallas on TPU, fused jnp off-TPU)
            from .pallas.flash_attention import flash_mha

            mesh = getattr(self, "_mesh", None)
            if (
                mesh is not None
                and mesh.devices.size > 1
                and jax.default_backend() == "tpu"
            ):
                # GSPMD cannot partition a pallas_call: shard over the
                # batch/head mesh axes explicitly (both embarrassingly
                # parallel for attention)
                return self._flash_sharded(qh, kh, vh, scale, mesh)
            return flash_mha(qh, kh, vh, scale, p.causal)
        kv_appended = kh.shape[1] - self.inputs[1].shape.logical_shape[1]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) * scale
        if p.causal:
            qlen, klen = scores.shape[-2], scores.shape[-1]
            # appended bias_kv/zero_attn keys are always attendable;
            # real keys follow absolute-position causality
            mask = jnp.tril(jnp.ones((qlen, klen), bool))
            if p.sliding_window:
                mask &= ~jnp.tril(jnp.ones((qlen, klen), bool),
                                  -p.sliding_window)
            if kv_appended:
                mask = mask.at[:, klen - kv_appended:].set(True)
            scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
        probs = jax.nn.softmax(scores, axis=-1)
        if use_dropout:
            keep = 1.0 - p.dropout
            probs = probs * jax.random.bernoulli(rng, keep, probs.shape) / keep
        return jnp.einsum("bhqk,bkhd->bqhd", probs, vh)

    def _flash_sharded(self, qh, kh, vh, scale, mesh):
        """shard_map-wrapped flash attention over batch/head axes."""
        import functools

        from jax.sharding import PartitionSpec

        from .pallas.flash_attention import flash_mha

        p: MultiHeadAttentionParams = self.params
        batch_spec, _, head_spec = self._view_specs()
        spec = PartitionSpec(batch_spec, None, head_spec, None)
        fn = functools.partial(flash_mha, scale=scale, causal=p.causal)
        return jax.shard_map(
            fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )(qh, kh, vh)

    def flops(self):
        p: MultiHeadAttentionParams = self.params
        b, s, e = self.inputs[0].shape.logical_shape
        ks = self.inputs[1].shape.logical_shape[1]
        # q (and its gate) and the output per query head, k and v per
        # key/value head
        gate = (p.v_channels if p.output_gate else 0) + int(p.head_gate)
        proj = 2.0 * b * s * e * (
            p.num_heads * (p.k_channels + gate + p.v_channels)
            + p.kv_heads * (p.k_channels + p.v_channels))
        if p.sliding_window:
            ks = min(ks, p.sliding_window)
        attn = 2.0 * b * p.num_heads * s * ks * (p.k_channels + p.v_channels)
        return proj + attn

    def dispatch_group(self):
        return "swa" if self._ring() else None

    @classmethod
    def dispatch_group_of(cls, ops, *, family, batch_slots, prefill_chunk,
                          state_bytes, **twin):
        """The window layers' rings: `swa_rows_live`, the ring rows some
        query of the dispatch sees, summed over the rows and the layers
        (`window_rows_live`), against `swa_rows_read`, the rows the
        program as built reads (every slot's whole ring, a layer)."""
        window, ring = ops[0].params.sliding_window, ops[0]._window_ring
        if prefill_chunk > ring - window:
            from ..config import ConfigError

            raise ConfigError(
                f"prefill_chunk {prefill_chunk} is longer than the "
                f"{ring - window} rows that {family}'s window layers' "
                f"rings hold beside their window (ring {ring}, "
                f"sliding_window {window}): a pass writes its rows before "
                "it reads, and a longer one would overwrite keys its first "
                "queries still see; build the model with that "
                "prefill_chunk")
        n = len(ops)

        def counts(positions, counts, chunk):
            return {"swa_rows_live": n * window_rows_live(window, positions,
                                                          counts),
                    "swa_rows_read": n * batch_slots * ring}

        return DispatchGroup(
            geometry={"window": window, "ring": ring}, counts=counts,
            build_args={"swa_state_bytes": state_bytes},
            gauges={"swa_state_bytes": state_bytes, "swa_ring_rows": ring})
