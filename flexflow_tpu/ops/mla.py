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
  `key_pos <= seq_lens[i] + j`.  Without an indexer the pool is read
  over the TABLE'S WIDTH (`take(pool, block_table)`); with one, by
  SELECTION (below).  Either way by gather: `kv_kernel` "gather" and
  "pallas" are the same formulation here (no in-place latent kernel is
  written; Mosaic refused a 64-wide slice in PR 28, and the rope part
  is 64 wide).

Selected keys (`MLAParams.index_topk > 0`: learned sparse attention, the
published "lightning indexer").  A query at position t attends the set S_t of
the `min(t + 1, index_topk)` causal keys a cheap INDEXER scores highest,
and no others; softmax, values and `W_o` are the block's, over S_t:

    q^I_j = RoPE(c_q W^I_q)_j   j = 1..index_n_heads   [index_head_dim]
    k^I_s = RoPE(LayerNorm(h_s W^I_k))      ONE key a token, all heads
    w_j   = (h W^I_w)_j * index_n_heads^-0.5 * index_head_dim^-0.5
    I(t, s) = sum_j w_j ReLU(q^I_j . k^I_s)    float32,  s <= t
    S_t = the top min(t + 1, index_topk) of I(t, .)     exact, a SET

(RoPE on the first `qk_rope_head_dim` channels of an index head, the
block's own frequencies; `c_q` is the block's query bottleneck, computed
once for both.)  `MLAParams.indexer` is the op's ROLE: `"full"` owns the
indexer's five weights, scores, picks, and hands the picks on as a
SECOND OUTPUT `[b, s, index_topk] int32` (key positions, order free,
`-1` beyond the `min(t + 1, index_topk)` real ones); `"shared"` has no
indexer weights and takes a `full` layer's picks as its LAST INPUT.
Paged, a `full` op keeps a second pool `index_cache [num_blocks, page,
index_head_dim]` beside `latent_cache`, written at the same `(block,
offset)` under the same `block_table` / `seq_lens` and named by
`cache_entries()`, so block bytes, copy-on-write, export and the prefix
cache cover both (`k^I` is a function of the prefix alone); a step
writes ALL its tokens' latents and index keys before any read, scores
its queries against the row's index keys in blocks of heads (no
`[b, s, heads, n]` float32 tensor exists), takes `lax.top_k`, and every
op with picks reads them one of three ways, by the step's shapes and
the backend (`selected_plan`): IN PLACE on a TPU (`ops/pallas/
selected_attention.py`: a kernel walks the row's LIVE pages of the pool
once for the chunk's `s x heads` query rows, the picks a one-byte mask
`[b, s, n]` inside its fold, float32 scores that never leave VMEM, a
trip count that is the row's length; every step under a table of up to
~24 k positions, a chunk of 16 up to ~64 k); BY SELECTION,
`pool[block_table[i, idx // page], idx % page]`: `[b, s, index_topk,
.]`, not the table's width (the CPU tier's decode step, and every step
under a table long enough); or the
row's view gathered once over the table's width with the picks as a
mask on dense scores (the CPU tier's prefill chunk under a table of a
few times `index_topk`, and the walk's parity oracle: a token row is
1.3 kB and the gather moves rows one by one).  Such an
op's pool rows are padded to whole lane tiles (`pool_width`).  Where the
keys in reach are no more than `index_topk` (`decode_max_seq`, or
stateless the sequence length) selection is the identity BY SHAPE: the
op traces the dense read above, no index pool is built, nothing is
scored, and the picks a `full` op hands on are the causal positions
themselves.  Stateless beyond that, the picks become a `[s, s]` mask on
the einsum core (short sequences; the flash kernels take no mask).

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
from .op import DispatchGroup, Op, ShapeError, WeightSpec
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
    #: learned sparse attention (module docstring "Selected keys"):
    #: keys a query reads, 0 = every live key; the indexer's heads and
    #: their width; and the op's role, "full" (scores, picks, hands the
    #: picks on) or "shared" (reads the picks it is handed)
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    indexer: str = ""

    @property
    def latent_width(self) -> int:
        """Values a token leaves in the cache, a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim


def selection_counts(index_topk: int, positions, counts) -> dict:
    """What a dispatch that advances row i over `positions[i] .. +
    counts[i] - 1` asks of ONE layer that reads selected keys, from
    host-owned lengths: `keys_live`, the keys a dense read would attend
    (`t + 1` a query at position t), `keys_selected`, the keys attended
    (`min(t + 1, index_topk)`), and `rows_past_topk`, the rows with a
    query past `index_topk` keys."""
    first = np.asarray(positions, np.int64)
    n = np.asarray(counts, np.int64)
    last = first + n  # keys the row's last query sees
    upto = lambda t: t * (t + 1) // 2  # noqa: E731  1 + .. + t
    live = upto(last) - upto(first)
    under = np.minimum(last, index_topk)
    selected = (upto(under) - upto(np.minimum(first, index_topk))
                + index_topk * (last - np.maximum(under, first)))
    return {"keys_live": int(live.sum()),
            "keys_selected": int(np.where(n > 0, selected, 0).sum()),
            "rows_past_topk": int(((n > 0) & (last > index_topk)).sum())}


def _rows_set(pool, where, values):
    """`pool [blocks, page, width]` with its token rows `where [r]`
    (`block * page + offset`) set to `values [.., width]` (r rows): the
    pool as ONE table of token rows, scattered by a flat index (the
    form whose operand keeps the pool's own layout)."""
    rows = pool.reshape(-1, pool.shape[-1])
    rows = rows.at[where].set(values.reshape(-1, pool.shape[-1])
                              .astype(pool.dtype))
    return rows.reshape(pool.shape)


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

    def _selects(self, keys: int) -> bool:
        """Whether a query with `keys` keys in reach reads a selection:
        up to `index_topk` of them selection is the identity (module
        docstring), and the op traces what it does without an
        indexer."""
        return 0 < self.params.index_topk < keys

    def reads_selection(self) -> bool:
        """Whether the paged op as built reads picked keys (the serving
        tier's counters ask)."""
        return self._paged() and self._selects(self._decode_max_seq)

    def pool_width(self) -> int:
        """Values a token's row of `latent_cache` holds: the latent, and
        where the op reads by selection, zeros up to whole 128-lane
        tiles behind it (640 for 576).  The device lays an array whose
        last axis is no whole number of tiles out with ANOTHER axis in
        the lanes (bf16 `[blocks, 16, 576]`: the blocks), so a read or
        write of token rows first copies the whole pool into row-major
        order and back, in every layer of every pass: 0.47 GB each way
        at the size the selection is for.  The table-wide read keeps
        its pool as it is."""
        w = self.params.latent_width
        return -(-w // 128) * 128 if self.reads_selection() else w

    def _index_pool(self) -> bool:
        """A `full` op's second pool, where selection can bite."""
        return (self.params.indexer == "full"
                and self._selects(self._decode_max_seq))

    def cache_entries(self):
        if not self._paged():
            return ()
        return ("latent_cache",) + (("index_cache",)
                                    if self._index_pool() else ())

    def infer_output_shapes(self, input_shapes):
        p: MLAParams = self.params
        x, *pos = input_shapes
        picks = [pos.pop()] if p.indexer == "shared" and pos else []
        xd = [d for d in x.dims if not d.is_replica_dim]
        if p.indexer not in ("", "full", "shared") \
                or bool(p.indexer) != (p.index_topk > 0) \
                or (p.indexer and (p.nope or not p.q_lora_rank)) \
                or (p.indexer == "shared") != bool(picks) \
                or (p.indexer == "full" and (
                    p.index_n_heads < 1
                    or p.index_head_dim < p.qk_rope_head_dim)):
            raise ShapeError(
                f"{self.name}: selected keys need index_topk > 0 with a "
                "role (indexer 'full': index_n_heads heads of "
                "index_head_dim >= qk_rope_head_dim; 'shared': the picks "
                "as the last input), positions and a query bottleneck; "
                f"got {p} and {len(input_shapes)} inputs")
        if any(q.logical_shape != x.logical_shape[:2] + (p.index_topk,)
               for q in picks):
            raise ShapeError(
                f"{self.name}: expect picks [batch, seq, index_topk], "
                f"got {[q.logical_shape for q in picks]}")
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
        if p.indexer != "full":
            return [x]
        return [x, ParallelTensorShape(
            tuple(ParallelDim(d.size, d.degree) for d in xd[:2])
            + (ParallelDim(p.index_topk, 1),
               ParallelDim(1, x.replica_degree, is_replica_dim=True)),
            DataType.INT32)]

    def _query_weights(self) -> int:
        """Weights that make the queries: wq_a, q_norm, wq_b, or one wq."""
        return 3 if self.params.q_lora_rank else 1

    def _indexer_weights(self) -> int:
        """A `full` op's own: wq_idx, wk_idx, k_idx_norm, k_idx_bias,
        w_idx."""
        return 5 if self.params.indexer == "full" else 0

    def num_trainable_weights(self) -> int:
        return self._query_weights() + 4 + self._indexer_weights()

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
        if p.indexer == "full":
            hi, di = p.index_n_heads, p.index_head_dim
            specs += [
                WeightSpec("wq_idx", w(p.q_lora_rank, hi, di), init),
                WeightSpec("wk_idx", w(e, di), init),
                WeightSpec("k_idx_norm", w(di), one),
                WeightSpec("k_idx_bias", w(di), ZeroInitializer()),
                WeightSpec("w_idx", w(e, hi), init),
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
                       w(nb, page, self.pool_width(), replica=one_rep),
                       zero),
        ] + ([WeightSpec("index_cache",
                         w(nb, page, p.index_head_dim, replica=one_rep),
                         zero)] if self._index_pool() else []) + [
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
        picks = positions.pop() if p.indexer == "shared" else None
        nq = self._query_weights()
        wkv_a, kv_norm, wkv_b, wo = weights[nq:nq + 4]
        state = weights[nq + 4 + self._indexer_weights():]
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
        if self._selects(self._decode_max_seq if self._paged()
                         else x.shape[1]):
            index = (self._index_inputs(
                x, cq, positions[0], weights[nq + 4:nq + 9])
                if p.indexer == "full" else None)
            return self._forward_selected(x, q_nope, q_rope, latent, wkv_b,
                                          wo, index, picks, state)
        outs = self._forward_every_key(x, q_nope, q_rope, latent, wkv_b,
                                       wo, state)
        if p.indexer == "full":
            # selection is the identity: the picks are the causal keys
            first = (state[-1].reshape(-1, 1).astype(jnp.int32)
                     if self._paged() else 0)
            at = first + jnp.arange(x.shape[1], dtype=jnp.int32)
            at = jnp.broadcast_to(at, x.shape[:2])[..., None]
            k = jnp.arange(p.index_topk, dtype=jnp.int32)
            outs.insert(1, jnp.where(k <= at, k, -1))
        return outs

    def _forward_every_key(self, x, q_nope, q_rope, latent, wkv_b, wo,
                           state):
        """Every live key attended (no indexer, or no more keys in reach
        than `index_topk`): [out, *state]."""
        p: MLAParams = self.params
        dn, rk = p.qk_nope_head_dim, p.kv_lora_rank
        if self._paged():
            pool, btab, slen = state
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

    # -- selected keys ----------------------------------------------------
    def _index_inputs(self, x, cq, positions, weights):
        """The indexer's side of a step's tokens: (q^I [b, s, heads, d],
        k^I [b, s, d], w [b, s, heads] float32); RoPE on the first
        `qk_rope_head_dim` channels of each."""
        p: MLAParams = self.params
        wq_idx, wk_idx, k_gain, k_bias, w_idx = weights
        dr = p.qk_rope_head_dim

        def turn(t):
            return jnp.concatenate(
                [rope(t[..., :dr], positions, p), t[..., dr:]], axis=-1)

        with scope("index_proj"):
            qi = turn(jnp.einsum("bsr,rjd->bsjd", cq, wq_idx))
            k = jnp.matmul(x, wk_idx).astype(jnp.float32)
            k = k - jnp.mean(k, axis=-1, keepdims=True)
            k = k * jax.lax.rsqrt(
                jnp.mean(jnp.square(k), axis=-1, keepdims=True) + p.eps)
            ki = turn((k * k_gain.astype(jnp.float32)
                       + k_bias.astype(jnp.float32)).astype(x.dtype))
            w = jnp.matmul(x, w_idx).astype(jnp.float32) * (
                p.index_n_heads ** -0.5 * p.index_head_dim ** -0.5)
        return qi, ki, w

    #: bytes of per-head index scores that may exist at once
    INDEX_SCORE_BYTES = 1 << 28

    def _index_picks(self, qi, w, keys, at):
        """Queries `qi [b, s, heads, d]` with head weights `w` at key
        positions `at [b, s]` against `keys [b, n, d]` (n > index_topk)
        -> picks `[b, s, index_topk]` int32: the positions of the
        `min(at + 1, index_topk)` keys `<= at` with the largest
        `sum_j w_j ReLU(q_j . k)` (float32), `-1` beyond them.  Scored
        a block of heads at a time, as many as `INDEX_SCORE_BYTES` of
        `[b, s, heads, n]` float32 hold."""
        p: MLAParams = self.params
        b, s, heads, _ = qi.shape
        n = keys.shape[1]
        with scope("index_scores"):
            most = max(1, self.INDEX_SCORE_BYTES // (4 * b * s * n))
            block = max(g for g in range(1, heads + 1)
                        if heads % g == 0 and g <= most)
            total = 0.0
            for j in range(0, heads, block):
                scores = jnp.einsum(
                    "bsjd,bnd->bsjn", qi[:, :, j:j + block], keys,
                    preferred_element_type=jnp.float32)
                total = total + jnp.sum(
                    jax.nn.relu(scores) * w[:, :, j:j + block, None], axis=2)
            reach = jnp.arange(n, dtype=jnp.int32) <= at[..., None]
            total = jnp.where(reach, total, jnp.finfo(jnp.float32).min)
        with scope("topk"):
            idx = jax.lax.top_k(total, p.index_topk)[1].astype(jnp.int32)
            return jnp.where(idx <= at[..., None], idx, -1)

    def _forward_selected(self, x, q_nope, q_rope, latent, wkv_b, wo,
                          index, picks, state):
        """More keys in reach than `index_topk`: a `full` op (`index`:
        `_index_inputs`'s three) scores and picks, a `shared` one was
        handed `picks`; both attend the picked keys alone.  [out,
        (a full op's picks), *state]."""
        p: MLAParams = self.params
        b, s = x.shape[:2]
        if not self._paged():
            at = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
            if index is not None:
                picks = self._index_picks(index[0], index[2], index[1], at)
            with scope("core"):
                # the picks as a mask on the einsum core: short rows
                keep = jnp.zeros((b, s, s), bool).at[
                    jnp.arange(b)[:, None, None],
                    jnp.arange(s)[None, :, None],
                    jnp.where(picks >= 0, picks, s)].set(True, mode="drop")
                rk = p.kv_lora_rank
                kvh = jnp.einsum("bsc,chd->bshd", latent[..., :rk], wkv_b)
                ctx = self._attend_dense(q_nope, q_rope, kvh,
                                         latent[..., rk:], keep[:, None])
            with scope("out"):
                out = jnp.einsum("bqhd,hde->bqe", ctx, wo).astype(x.dtype)
            return [out] + ([picks] if index is not None else [])
        pool, *index_pool, btab, slen = state
        page = self._kv_page_size
        n = btab.shape[1] * page
        with scope("state_write"):
            # ALL the step's tokens before any read, at the same
            # (block, offset) in both pools; `_attend_paged_chunk`'s pad
            # contract
            at = (slen.reshape(b, 1).astype(jnp.int32)
                  + jnp.arange(s, dtype=jnp.int32))  # [b, s]
            row = jnp.minimum(at, n - 1)
            blk = jnp.where(at < n,
                            jnp.take_along_axis(btab, row // page, axis=1), 0)
            where = (blk * page + row % page).reshape(b * s)
            pool = _rows_set(pool, where, jnp.pad(latent, (
                (0, 0), (0, 0), (0, pool.shape[-1] - latent.shape[-1]))))
            if index is not None:
                index_pool = [_rows_set(index_pool[0], where, index[1])]
        if index is not None:
            with scope("index_scores"):
                keys = jnp.take(index_pool[0], btab, axis=0) \
                    .reshape(b, n, -1).astype(x.dtype)
            picks = self._index_picks(index[0], index[2], keys, at)
        with scope("selected_read"):
            plan = self.selected_plan(s, n)
            if plan == "walk":
                ctx = self._attend_walk(q_nope, q_rope, wkv_b, pool, btab,
                                        slen, picks)
            else:
                attend = (self._attend_selected if plan == "gather"
                          else self._attend_masked_view)
                ctx = attend(q_nope, q_rope, wkv_b, pool, btab, picks)
        with scope("out"):
            out = jnp.einsum("bshd,hde->bse", ctx, wo).astype(x.dtype)
        return ([out] + ([picks] if index is not None else [])
                + [pool, *index_pool, btab, slen])

    #: what the three formulations of the read cost a layer on the chip,
    #: in nanoseconds (v5e, 32 rows under a table of 12,800; PERF.md
    #: section 6).  `scripts/serve_step_probe.py --selected-read
    #: gather,view`, PR 57, both step programs: a picked key gathered and
    #: attended; a key of the table's width gathered into the row's
    #: view; a (query, key) pair of the dense masked scores over that
    #: view.  `scripts/selected_read_probe.py --forms walk`, PR 58, the
    #: kernel's launch alone at chunks of 16 and 1, rows from parked to
    #: the table's width: a key of a row's live pages walked (copied, its
    #: value row cleared, its 64 heads' scores against one query), and a
    #: further (query, key) pair of the fold (a tile of 512 keys under
    #: 1,024 query rows in 7.6 us: 159 TFLOP/s); `serve_step_probe.py
    #: --selected-read plan,walk` read the decode step at 28.6 ms
    #: gathering and 25.0 walking
    GATHER_NS_A_PICK, VIEW_NS_A_KEY, DENSE_NS_A_PAIR = 29.0, 4.3, 1.5
    WALK_NS_A_KEY, WALK_NS_A_PAIR = 1.7, 0.82

    def selected_plan(self, s: int, n: int) -> str:
        """"walk", "gather" or "view": how a step of `s` tokens a row
        reads its picked keys under a table of `n` positions, from the
        shapes and the backend alone.  The gather moves `s x index_topk`
        token rows a row of the batch, one by one (1.3 kB each: 19 ns a
        row in XLA's gather, whatever the bandwidth); the view moves the
        table's `n` rows page by page ONCE and pays a masked dense
        product for every pair; the walk (a TPU's: `ops/pallas/
        selected_attention.py`) copies the row's LIVE pages once and
        folds them under the picks' mask, costed here at the table's
        width, which is all a shape says of a row's length.  On the TPU
        at 12,800 positions and 2,048 picks every step walks (a chunk
        of 16: 2.4 ms a layer at the cell's mix of lengths, where the
        view took 15); the decode step gathers again from ~24 k
        positions, a chunk of 16 from ~64 k.  Elsewhere (the CPU tier)
        the decode step gathers and a chunk under a short table takes
        the view."""
        k = self.params.index_topk
        costs = {"gather": self.GATHER_NS_A_PICK * s * k,
                 "view": n * (self.VIEW_NS_A_KEY + self.DENSE_NS_A_PAIR * s)}
        if jax.default_backend() == "tpu":
            from .pallas.selected_attention import walk_fits

            if walk_fits(s, self._kv_page_size, self.pool_width()):
                costs["walk"] = n * (self.WALK_NS_A_KEY
                                     + self.WALK_NS_A_PAIR * s)
        return min(costs, key=costs.get)

    def _latent_queries(self, q_nope, q_rope, wkv_b, width: int):
        """The queries taken into the latent space, `[b, s, h, width]`:
        `[q_nope W_kvb_k^T | q_rope | 0]`, zeros against the padding of
        the pool's rows (`pool_width`)."""
        p: MLAParams = self.params
        q_lat = jnp.einsum("bshd,chd->bshc", q_nope,
                           wkv_b[..., :p.qk_nope_head_dim])
        pad = jnp.zeros(q_rope.shape[:-1] + (width - p.latent_width,),
                        q_rope.dtype)
        return jnp.concatenate([q_lat, q_rope, pad], axis=-1)

    def _picks_mask(self, picks, n: int, dtype):
        """`picks [b, s, k]` (positions, `-1` = none) as a mask `[b, s,
        n]` bool over the table's positions.  A product, not a scatter
        (1 M scalar updates took 17 ms a `[32, 16, 2048]` set of picks):
        a position is `hi x 128 + lo`, and `one_hot(hi)^T one_hot(lo)`
        summed over a query's picks is 1 exactly where a pick stands
        (`-1` is no row of either).  The layers that share a set of
        picks share the mask: one computation of one tensor, which XLA
        keeps once."""
        b, s, _ = picks.shape
        lanes = 128
        hi = jax.nn.one_hot(picks // lanes, -(-n // lanes), dtype=dtype)
        lo = jax.nn.one_hot(jnp.where(picks >= 0, picks % lanes, -1), lanes,
                            dtype=dtype)
        keep = jnp.einsum("bskh,bskl->bshl", hi, lo,
                          preferred_element_type=jnp.float32)
        return keep.reshape(b, s, -1)[..., :n] > 0

    def _attend_walk(self, q_nope, q_rope, wkv_b, pool, btab, slen, picks):
        """The same read in place (`ops/pallas/selected_attention.py`):
        the kernel walks each row's LIVE pages of the pool once for the
        chunk's `s x heads` query rows, with the picks as a mask inside
        its fold; no view over the table's width and no scores in HBM.
        The value up-projection stays here."""
        from .pallas.selected_attention import selected_latent_attention

        p: MLAParams = self.params
        n = btab.shape[1] * self._kv_page_size
        out_lat = selected_latent_attention(
            self._latent_queries(q_nope, q_rope, wkv_b, pool.shape[-1]),
            pool, btab, slen, self._picks_mask(picks, n, q_nope.dtype),
            softmax_scale(p), p.kv_lora_rank)
        return jnp.einsum("bshc,chd->bshd", out_lat.astype(q_nope.dtype),
                          wkv_b[..., p.qk_nope_head_dim:])

    def _attend_masked_view(self, q_nope, q_rope, wkv_b, pool, btab, picks):
        """The same read as `_attend_selected` by the table's width:
        the row's view `[b, n, .]` gathered once, page by page, dense
        scores `[b, h, s, n]`, and the picks as a MASK on them
        (`_picks_mask`).  The CPU tier's formulation of a chunk's read,
        and the walk's parity oracle."""
        p: MLAParams = self.params
        dn, rk = p.qk_nope_head_dim, p.kv_lora_rank
        b, n = btab.shape[0], btab.shape[1] * self._kv_page_size
        view = jnp.take(pool, btab, axis=0).reshape(b, n, -1) \
            .astype(q_nope.dtype)
        keep = self._picks_mask(picks, n, q_nope.dtype)
        scores = jnp.einsum(
            "bshc,bnc->bhsn",
            self._latent_queries(q_nope, q_rope, wkv_b, pool.shape[-1]),
            view, preferred_element_type=jnp.float32) * softmax_scale(p)
        scores = jnp.where(keep[:, None], scores,
                           jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(scores, axis=-1).astype(q_nope.dtype)
        out_lat = jnp.einsum("bhsn,bnc->bshc", probs, view[..., :rk])
        return jnp.einsum("bshc,chd->bshd", out_lat, wkv_b[..., dn:])

    def _attend_selected(self, q_nope, q_rope, wkv_b, pool, btab, picks):
        """Queries `[b, s, heads, .]` over their own picked keys
        `picks [b, s, k]` (positions, `-1` = none), read out of the pool
        BY SELECTION, `pool[btab[i, idx // page], idx % page]`: `[b, s,
        k, rank + rope]`, with `W_kvb` absorbed as `_attend_paged`
        absorbs it.  A pick is a key written before this read (the
        step's own tokens included); a `-1` reads block `btab[i, 0]`'s
        first row and is masked out of the softmax exactly."""
        p: MLAParams = self.params
        dn, rk = p.qk_nope_head_dim, p.kv_lora_rank
        b, s, k = picks.shape
        page = self._kv_page_size
        idx = jnp.maximum(picks, 0)
        blk = jnp.take_along_axis(
            btab, (idx // page).reshape(b, s * k), axis=1).reshape(b, s, k)
        picked = jnp.take(pool.reshape(-1, pool.shape[-1]),
                          blk * page + idx % page,
                          axis=0).astype(q_nope.dtype)
        scores = jnp.einsum(
            "bshc,bskc->bhsk",
            self._latent_queries(q_nope, q_rope, wkv_b, pool.shape[-1]),
            picked, preferred_element_type=jnp.float32) * softmax_scale(p)
        scores = jnp.where((picks >= 0)[:, None], scores,
                           jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(scores, axis=-1).astype(q_nope.dtype)
        out_lat = jnp.einsum("bhsk,bskc->bshc", probs, picked[..., :rk])
        return jnp.einsum("bshc,chd->bshd", out_lat, wkv_b[..., dn:])

    def core_plan(self) -> str:
        """"flash" or "dense": the stateless path's causal core for the
        op's declared sequence length, `MultiHeadAttention`'s rule
        (`flash_min_seq`, set on every op at compile)."""
        from ..config import DEFAULT_FLASH_MIN_SEQ

        s = self.inputs[0].shape.logical_shape[1]
        flash_min = getattr(self, "_flash_min_seq", DEFAULT_FLASH_MIN_SEQ)
        return "flash" if s >= flash_min else "dense"

    def _attend_dense(self, q_nope, q_rope, kvh, k_rope, keep=None):
        """einsum, softmax, einsum with the `[b, h, s, s]` scores in
        HBM: short rows.  `keep` (`[b, 1, s, s]`: the picked keys)
        stands where the causal triangle does."""
        p: MLAParams = self.params
        dn = p.qk_nope_head_dim
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, kvh[..., :dn],
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope,
                               preferred_element_type=jnp.float32))
        s = q_nope.shape[1]
        if keep is None:
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
        n = self._decode_max_seq if self._paged() else s
        if self._selects(n) and p.indexer == "full":
            # the indexer's queries, key and head weights, and a score
            # a head for every key in reach
            hi, di = p.index_n_heads, p.index_head_dim
            proj += 2.0 * b * s * (p.q_lora_rank * hi * di + e * di
                                   + e * hi + n * hi * di)
        if self._paged():
            # absorbed: the query into the latent and the values out of
            # it, scores and the weighted sum over the keys read: the
            # gathered view, or the picked keys alone
            keys = p.index_topk if self._selects(n) else n
            return proj + 2.0 * b * s * h * (
                p.kv_lora_rank * (p.qk_nope_head_dim + p.v_head_dim)
                + keys * (p.latent_width + p.kv_lora_rank))
        expand = 2.0 * b * s * p.kv_lora_rank * h * (
            p.qk_nope_head_dim + p.v_head_dim)
        return proj + expand + 2.0 * b * h * s * s * (dq + p.v_head_dim)

    def dispatch_group(self):
        return "dsa" if self.reads_selection() else None

    @classmethod
    def dispatch_group_of(cls, ops, *, batch_slots, page_size, max_seq,
                          **twin):
        """The selection (`selection_counts`, a layer's):
        `dsa_keys_live` and `dsa_keys_selected` a layer,
        `dsa_keys_scored` (the indexers score every live key:
        `keys_live` x the full layers), `dsa_rows_past_topk`,
        `index_blocks_live`, the advancing rows' blocks of index keys,
        summed over the full layers, and `dsa_keys_read`, the keys a
        layer's read touches under the plan the program's shape takes
        (`selected_plan`): under the walk the advancing rows' live keys,
        `positions[i] + chunk` each, once a chunk; under the view every
        slot's table width; under the gather every slot's `chunk x
        index_topk` picks."""
        topk = ops[0].params.index_topk
        full = sum(op.params.indexer == "full" for op in ops)
        plans = {}  # {chunk: the plan its program took}

        def counts(positions, counts, chunk):
            one = selection_counts(topk, positions, counts)
            n = np.asarray(counts, np.int64)
            first = np.asarray(positions, np.int64)
            blocks = -(-(first + n) // page_size)
            if chunk not in plans:
                plans[chunk] = ops[0].selected_plan(chunk, max_seq)
            if plans[chunk] == "walk":
                read = int(np.minimum(first + chunk, max_seq)[n > 0].sum())
            else:
                read = batch_slots * (max_seq if plans[chunk] == "view"
                                      else chunk * topk)
            return {"dsa_keys_live": one["keys_live"],
                    "dsa_keys_selected": one["keys_selected"],
                    "dsa_keys_scored": full * one["keys_live"],
                    "dsa_rows_past_topk": one["rows_past_topk"],
                    "index_blocks_live": full * int(blocks[n > 0].sum()),
                    "dsa_keys_read": read}

        return DispatchGroup(geometry={"topk": topk, "full_layers": full},
                             counts=counts)
