"""EVA attention (Zheng et al., "Efficient Attention via Control
Variates", ICLR 2023) in the chunked form EvaByte serves: ONE softmax
over two kinds of key.  With `c = chunk_size`, `w = window_size`
(`c` divides `w`), `s = head_dim ** -0.5`, per head, after the rotary
embedding of q and k at their absolute positions:

    summaries, one a chunk m of c positions (learned `phi`, `mu`):
        a_j = softmax_{j in chunk m}(s <k_j, phi>)
        K_m = sum_j a_j k_j + mu;   V_m = sum_j a_j v_j
    query i, W = i // w:
        singletons  S_i = {j : j // w == W, j <= i}   (its own window)
        summaries   C_i = {m : m < (w / c) W}         (earlier windows)
        p = softmax([s <q_i, k_j> | s <q_i, K_m>])
        o_i = sum_{S_i} p_ij v_j + sum_{C_i} p_im V_m

So a query reads at most `w` singletons and one summary per `c`
positions before its window: what a sequence keeps is not a cache that
grows a row a token and not a state of fixed size, but

* a WINDOW of keys and values that fills for `w` positions and then
  starts again (`win_k`, `win_v` `[slots, w, heads, d]`, position `t`
  at row `t mod w`), and
* a STORE of summaries that grows one row every `c` positions
  (`sum_k`, `sum_v` `[slots, max_seq / c, heads, d]`, chunk `m` at row
  `m`) and is read only up to the last window boundary, and
* the last `c` positions' keys and values once more, a few KB
  (`pend_k`, `pend_v` `[slots, c, heads, d]`, position `t` at row
  `t mod c`): the chunk that is still filling, from which the step that
  holds its last position pools it without a read of the window.

All are per slot (`slot_state_entries`), live in the compute dtype,
position-major (a position's heads lie together: the step's write is
one row, and the decode step's read takes them as they lie; the
prefill pass's batched products have the TPU compiler re-lay a window
head-major first, 0.27 GB a layer and array: head-major arrays would
move that copy to both sides of every step's write) and are MASKED BY
POSITION: what a row may read follows from the sequence's
own `seq_lens` alone, so a new window needs no clearing pass and a
slot's last tenant is never visible to its next (`slot_state_resets` is
False: the scheduler's reset at admission has nothing to zero here).

Two shapes, one set of weights:

* no state (`slot_state=False`): every row runs its whole `[b, s]`
  input from position 0, a window of queries at a time;
* per-slot state: a step of s tokens a row starts at the row's
  `seq_lens[i]` and advances by `row_tokens[i]` of them (host-owned: 0
  for an idle slot, 1 on a decode step or past the prompt in a pass, up
  to s on a chunk).  The step reads the window AS IT WAS, with its own
  keys beside it, and only then writes: a step may therefore start and
  end anywhere, across a chunk's or a window's end.  It writes the
  summary of every chunk whose LAST position it holds, before the read
  (a query past a window boundary sees the chunks the step has just
  completed).  s == 1 is the decode step, s == C a prefill chunk in one
  pass.

Everything is plain jax.numpy: the serving core reads every slot's
whole window and store whatever is live (`eva_row_counts` says how much
of that was live: the counters a later kernel is judged by).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..fftype import DataType, OperatorType
from ..initializer import DEFAULT_WEIGHT_INIT, ZeroInitializer
from ..obs.scopes import scope
from ..tensor import ParallelDim, ParallelTensorShape
from .attention import rotate_half
from .op import DispatchGroup, Op, ShapeError, ShardConfig, WeightSpec

#: the per-slot arrays, in the order the op takes and returns them
STATE = ("win_k", "win_v", "sum_k", "sum_v", "pend_k", "pend_v")


@dataclasses.dataclass(frozen=True)
class EvaAttentionParams:
    embed_dim: int
    num_heads: int
    head_dim: int
    window_size: int = 2048
    chunk_size: int = 16
    rope_theta: float = 10000.0


def summarise(k, v, phi, mu, scale: float):
    """k, v [..., c, h, d] (one chunk on axis -3), phi / mu [h, d] ->
    (K, V) [..., h, d] in float32: the chunk's keys and values pooled by
    a softmax over its positions of `scale <k_j, phi>`."""
    f32 = jnp.float32
    k, v = k.astype(f32), v.astype(f32)
    a = jax.nn.softmax(
        scale * jnp.einsum("...chd,hd->...ch", k, phi.astype(f32)), axis=-2)
    return (jnp.einsum("...ch,...chd->...hd", a, k) + mu.astype(f32),
            jnp.einsum("...ch,...chd->...hd", a, v))


def _softmax_over(parts, masks, scale: float):
    """One float32 softmax over the last axes of `parts` side by side,
    each under its mask; returns the probabilities split back."""
    z = jnp.concatenate(
        [jnp.where(m, scale * p.astype(jnp.float32), -jnp.inf)
         for p, m in zip(parts, masks)], axis=-1)
    p = jax.nn.softmax(z, axis=-1)
    return jnp.split(p, np.cumsum([x.shape[-1] for x in parts])[:-1],
                     axis=-1)


def eva_row_counts(window: int, chunk: int, store_rows: int, slots: int,
                   positions, counts) -> dict:
    """What a dispatch's EVA layers were asked to read and write, ONE
    LAYER'S worth, from host-owned lengths alone: row i advances over
    positions `positions[i] .. + counts[i] - 1` (numpy, [slots]).

    `eva_rows_window` / `eva_rows_summary`: singleton and summary rows
    visible to the advancing queries, summed over them;
    `eva_rows_read`: rows the program AS BUILT reads for them: every
    slot's whole window and whole store, once a dispatch;
    `eva_summaries_written`: chunks whose last position the dispatch
    holds."""
    pos = np.asarray(positions, np.int64)
    n = np.asarray(counts, np.int64)
    j = np.arange(int(n.max()) if n.size else 0)[None, :]
    live = j < n[:, None]
    at = pos[:, None] + j
    return {
        "eva_rows_window": int(((at % window + 1) * live).sum()),
        "eva_rows_summary": int(
            ((at // window) * (window // chunk) * live).sum()),
        "eva_rows_read": int(slots * (window + store_rows)),
        "eva_summaries_written": int(((pos + n) // chunk
                                      - pos // chunk).sum()),
    }


class EvaAttention(Op):
    op_type = OperatorType.EVA_ATTENTION
    #: its per-slot state is masked by the sequence's own positions:
    #: `build_slot_state_reset` has nothing to zero here
    slot_state_resets = False

    def __init__(self, params, inputs, name="", shard=None,
                 slot_state: bool = False, max_seq: int = 0):
        # must exist before Op.__init__ runs make_weight_specs
        self._slot_state = bool(slot_state)
        self._max_seq = int(max_seq)
        super().__init__(params, inputs, name=name,
                         shard=shard or ShardConfig())

    def ctor_kwargs(self) -> dict:
        return ({"slot_state": True, "max_seq": self._max_seq}
                if self._slot_state else {})

    def slot_state_entries(self):
        return STATE if self._slot_state else ()

    @property
    def store_rows(self) -> int:
        """Rows of `sum_k` / `sum_v` a slot: a chunk of `max_seq` each."""
        return self._max_seq // self.params.chunk_size

    def dispatch_group(self):
        return "eva" if self._slot_state else None

    @classmethod
    def dispatch_group_of(cls, ops, *, family, batch_slots, prefill_chunk,
                          state_bytes, **twin):
        """The windows and summary stores: `eva_row_counts`, summed over
        the layers."""
        p: EvaAttentionParams = ops[0].params
        window, chunk, store_rows = (p.window_size, p.chunk_size,
                                     ops[0].store_rows)
        if prefill_chunk > window:
            from ..config import ConfigError

            raise ConfigError(
                f"prefill_chunk {prefill_chunk} is longer than "
                f"{family}'s window_size {window}: a "
                "prefill pass reads the window as it was and writes it "
                "once, so it may cross one window boundary, not two")
        n = len(ops)

        def counts(positions, counts, chunk_tokens):
            one = eva_row_counts(window, chunk, store_rows, batch_slots,
                                 positions, counts)
            return {k: v * n for k, v in one.items()}

        return DispatchGroup(
            geometry={"window": window, "chunk": chunk,
                      "store_rows": store_rows},
            counts=counts, build_args={"eva_state_bytes": state_bytes})

    def infer_output_shapes(self, input_shapes):
        (x,) = input_shapes
        p: EvaAttentionParams = self.params
        xd = [d for d in x.dims if not d.is_replica_dim]
        if len(xd) != 3 or xd[2].size != p.embed_dim:
            raise ShapeError(f"{self.name}: expect [batch, seq, "
                             f"{p.embed_dim}], got {x.logical_shape}")
        if xd[1].degree != 1 or xd[2].degree != 1 \
                or not self.shard.is_trivial():
            raise ShapeError(
                f"{self.name}: sharded over the batch only (heads over "
                "a model axis are not built yet)")
        if p.chunk_size < 1 or p.window_size % p.chunk_size \
                or p.head_dim % 2:
            raise ShapeError(
                f"{self.name}: chunk_size {p.chunk_size} must divide "
                f"window_size {p.window_size} (a visible chunk is always "
                f"complete), and head_dim {p.head_dim} be even (rotary)")
        if self._slot_state:
            if xd[0].degree != 1:
                raise ShapeError(f"{self.name}: per-slot state needs an "
                                 "unsharded batch dim (slots are "
                                 "host-owned)")
            if self._max_seq < 1 or self._max_seq % p.chunk_size \
                    or not 1 <= xd[1].size <= p.window_size:
                raise ShapeError(
                    f"{self.name}: per-slot state needs max_seq "
                    f"({self._max_seq}) a multiple of chunk_size "
                    f"{p.chunk_size} and a step of 1..window_size "
                    f"{p.window_size} tokens, got {xd[1].size}")
        return [x]

    def num_trainable_weights(self) -> int:
        return 6

    def make_weight_specs(self, input_shapes):
        (x,) = input_shapes
        p: EvaAttentionParams = self.params
        slots = [d for d in x.dims if not d.is_replica_dim][0].size
        rep = ParallelDim(1, x.total_degree, is_replica_dim=True)

        def w(*sizes, dtype=x.dtype, replica=rep):
            return ParallelTensorShape(
                tuple(ParallelDim(s) for s in sizes) + (replica,), dtype)

        init, zero = DEFAULT_WEIGHT_INIT, ZeroInitializer()
        e, h, d = p.embed_dim, p.num_heads, p.head_dim
        specs = [
            # (flat: `[e, heads, d]` is tiled over its last two axes on
            # a TPU, and every step would re-lay it for the one product)
            WeightSpec("wq", w(e, h * d), init),
            WeightSpec("wk", w(e, h * d), init),
            WeightSpec("wv", w(e, h * d), init),
            WeightSpec("wo", w(h * d, e), init),
            WeightSpec("adaptive_phi", w(h, d), init),
            WeightSpec("adaptive_mu_k", w(h, d), init),
        ]
        if not self._slot_state:
            return specs
        one = ParallelDim(1, 1, is_replica_dim=True)
        window = w(slots, p.window_size, h, d, replica=one)
        store = w(slots, self.store_rows, h, d, replica=one)
        ints = w(slots, dtype=DataType.INT32, replica=one)
        pend = w(slots, p.chunk_size, h, d, replica=one)
        return specs + [
            WeightSpec("win_k", window, zero), WeightSpec("win_v", window, zero),
            WeightSpec("sum_k", store, zero), WeightSpec("sum_v", store, zero),
            WeightSpec("pend_k", pend, zero), WeightSpec("pend_v", pend, zero),
            WeightSpec("seq_lens", ints, zero),
            WeightSpec("row_tokens", ints, zero),
        ]

    def forward(self, inputs, weights, *, training=False, rng=None):
        (x,) = inputs
        p: EvaAttentionParams = self.params
        wq, wk, wv, wo, phi, mu = weights[:6]
        b, s = x.shape[:2]
        if self._slot_state:
            start = weights[12].reshape(b).astype(jnp.int32)
        else:
            start = jnp.zeros((b,), jnp.int32)
        at = start[:, None] + jnp.arange(s, dtype=jnp.int32)  # [b, s]
        with scope("proj"):
            flat = [jnp.matmul(x, w) for w in (wq, wk, wv)]
            if self._slot_state:
                # (without it the TPU compiler lays the products out for
                # the rotation behind them, and transposes each weight,
                # 34 MB at published widths, on every step)
                flat = jax.lax.optimization_barrier(flat)
            q, k, v = (t.reshape(b, s, p.num_heads, p.head_dim)
                       for t in flat)
            q = rotate_half(q, at, p.head_dim, p.rope_theta)
            k = rotate_half(k, at, p.head_dim, p.rope_theta)
        if self._slot_state:
            o, state = self._step(q, k, v, phi, mu, at, weights[6:])
        else:
            o, state = self._whole(q, k, v, phi, mu), []
        with scope("out"):
            out = jnp.matmul(o.astype(x.dtype).reshape(b, s, -1), wo)
        return [out.astype(x.dtype), *state]

    # -- no state: every row's whole sequence from position 0 ------------
    def _whole(self, q, k, v, phi, mu):
        """A window of queries at a time: its own window's keys under
        the causal mask beside the summaries of the windows before."""
        p: EvaAttentionParams = self.params
        b, s, h, d = q.shape
        w, c, scale = p.window_size, p.chunk_size, p.head_dim ** -0.5
        if s <= w:  # one window: no summary is ever visible
            nw, w = 1, s
        else:
            nw = -(-s // w)
        with scope("summarise"):
            m = (nw - 1) * (w // c) if nw > 1 else 0  # chunks ever visible
            K, V = summarise(k[:, :m * c].reshape(b, m, c, h, d),
                             v[:, :m * c].reshape(b, m, c, h, d),
                             phi, mu, scale)
        with scope("core"):
            pad = [(0, 0), (0, nw * w - s), (0, 0), (0, 0)]
            qw, kw, vw = (jnp.pad(t, pad).reshape(b, nw, w, h, d)
                          for t in (q, k, v))
            t = jnp.arange(w)
            own = jnp.einsum("bnshd,bnthd->bnhst", qw, kw)
            parts, masks = [own], [t[None, :] <= t[:, None]]
            if m:
                parts.append(jnp.einsum("bnshd,bmhd->bnhsm", qw,
                                        K.astype(q.dtype)))
                masks.append((jnp.arange(m)[None, :] < (
                    jnp.arange(nw) * (w // c))[:, None])[:, None, None, :])
            probs = _softmax_over(parts, masks, scale)
            o = jnp.einsum("bnhst,bnthd->bnshd", probs[0].astype(q.dtype), vw)
            if m:
                o = o + jnp.einsum("bnhsm,bmhd->bnshd",
                                   probs[1].astype(q.dtype), V.astype(q.dtype))
            return o.reshape(b, nw * w, h, d)[:, :s]

    # -- per-slot state: a step from each row's own position --------------
    def _step(self, q, k, v, phi, mu, at, state):
        p: EvaAttentionParams = self.params
        (win_k, win_v, sum_k, sum_v, pend_k, pend_v, seq_lens,
         row_tokens) = state
        b, s, h, d = q.shape
        w, c, scale = p.window_size, p.chunk_size, p.head_dim ** -0.5
        f32 = jnp.float32
        rows = jnp.arange(b)[:, None]
        start = at[:, 0]
        count = jnp.clip(row_tokens.reshape(b).astype(jnp.int32), 0, s)
        end = start + count  # the row's length after the step
        with scope("summarise"):
            # chunks whose last position lies in [start, end): at most
            # `most` a row, from the chunk that holds `start` on.  A
            # position before the step is pending at row `t mod c`, one
            # of the step's own sits behind those c rows
            most = -(-s // c)
            near_k = jnp.concatenate([pend_k.astype(k.dtype), k], axis=1)
            near_v = jnp.concatenate([pend_v.astype(v.dtype), v], axis=1)
            r = jnp.arange(c, dtype=jnp.int32)

            def near_row(t):  # [b, ...] positions -> rows of `near`
                first = start.reshape((b,) + (1,) * (t.ndim - 1))
                return jnp.where(t < first, t % c,
                                 jnp.minimum(c + t - first, c + s - 1))

            chunk = start[:, None] // c + jnp.arange(most, dtype=jnp.int32)
            done = (chunk + 1) * c <= end[:, None]
            idx = near_row((chunk * c)[..., None] + r)  # [b, most, c]
            K, V = summarise(near_k[rows[..., None], idx],
                             near_v[rows[..., None], idx], phi, mu, scale)
            where = jnp.where(done, chunk, sum_k.shape[1])  # else dropped
            sum_k = sum_k.at[rows, where].set(K.astype(sum_k.dtype),
                                              mode="drop")
            sum_v = sum_v.at[rows, where].set(V.astype(sum_v.dtype),
                                              mode="drop")
            # the last position under `end` of every residue (where the
            # step brought none, it is the one already pending)
            last = end[:, None] - 1 - (end[:, None] - 1 - r) % c
            pend_k = near_k[rows, near_row(last)].astype(pend_k.dtype)
            pend_v = near_v[rows, near_row(last)].astype(pend_v.dtype)
        with scope("core"):
            window_of = at // w  # [b, s]
            parts = [jnp.einsum("bshd,bthd->bhst", q, win_k,
                                preferred_element_type=f32),
                     jnp.einsum("bshd,bjhd->bhsj", q, k,
                                preferred_element_type=f32),
                     jnp.einsum("bshd,bmhd->bhsm", q, sum_k,
                                preferred_element_type=f32)]
            j = jnp.arange(s)
            masks = [
                # the window before this step: rows under the start's
                # offset, for the queries still in the start's window
                ((jnp.arange(w)[None, None, :] < (start % w)[:, None, None])
                 & (window_of == window_of[:, :1])[..., None])[:, None],
                # the step's own keys: causal, and of the query's window
                ((j[None, :] <= j[:, None])[None]
                 & (window_of[:, None, :] == window_of[:, :, None]))[:, None],
                (jnp.arange(sum_k.shape[1])[None, None, :]
                 < (window_of * (w // c))[..., None])[:, None],
            ]
            probs = [x.astype(q.dtype)
                     for x in _softmax_over(parts, masks, scale)]
            o = (jnp.einsum("bhst,bthd->bshd", probs[0], win_v,
                            preferred_element_type=f32)
                 + jnp.einsum("bhsj,bjhd->bshd", probs[1], v,
                              preferred_element_type=f32)
                 + jnp.einsum("bhsm,bmhd->bshd", probs[2], sum_v,
                              preferred_element_type=f32))
        with scope("state_write"):
            # the row's real tokens only; a pad's row index is dropped
            slot = jnp.where(jnp.arange(s)[None, :] < count[:, None],
                             at % w, w)
            win_k = win_k.at[rows, slot].set(k.astype(win_k.dtype),
                                             mode="drop")
            win_v = win_v.at[rows, slot].set(v.astype(win_v.dtype),
                                             mode="drop")
        return o, [win_k, win_v, sum_k, sum_v, pend_k, pend_v, seq_lens,
                   row_tokens]

    def flops(self):
        """The four products and, a position, scores and values over
        half a window of singletons and the summaries of half the
        sequence's chunks."""
        p: EvaAttentionParams = self.params
        b, s, e = self.inputs[0].shape.logical_shape
        hd = p.num_heads * p.head_dim
        keys = min(s, p.window_size) / 2 + s / (2 * p.chunk_size)
        return b * s * (8.0 * e * hd + 4.0 * hd * keys)
