"""Gated DeltaNet: a linear-attention mixer whose memory of a sequence
is a fixed-size state, not a cache that grows with it.

    [q, k, v, z] = h W_qkvz;  [b, a] = h W_ba
    [q, k, v] = silu(causal depthwise conv over time, kernel K)
    q, k repeated to the value heads;  q = l2norm(q) / sqrt(d_k),
    k = l2norm(k);  beta = sigmoid(b)
    g = -exp(A_log) * softplus(a + dt_bias)            per value head
    per head, S a [d_k, d_v] matrix, for each position in order:
        S = exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S = S + k_t d^T
        o_t = S^T q_t
    y = w_n * rmsnorm(o) * silu(z)  per head;  out = y W_out

What a sequence leaves behind, a layer: the conv's last K - 1 inputs
(`conv_state`, in the compute dtype) and `S` for every value head
(`rec_state`, float32, as the recurrence itself is).  Both are FIXED
SIZE: `slot_state_entries()` names them, the serving tier allocates
them `[slots, ...]` beside the paged pools, zeroes a slot's rows at
admission and never pages, copies, exports or reorders them
(docs/SERVING.md "Per-slot recurrent state").

Two shapes, one set of weights:

* no state (`slot_state=False`): every row starts from zero and runs
  its whole `[b, s]` input: what a trainer or a one-shot forward runs;
* per-slot state: a step of s tokens a row starts from the row's own
  state and returns it advanced by the row's `row_tokens[i]` tokens
  (host-owned, like `block_table`: 0 for an idle slot, 1 on a decode
  step or past the prompt in a pass, up to s on a chunk).  Steps past it
  leave a row's state exactly as it was (`exp(0) S + k 0`), so s == 1
  is the decode step and s == C a prefill chunk in one pass.

Which recurrence runs is chosen from what can be observed
(`ops/pallas/gated_delta_rule.py pick_recurrence`: backend, per-slot
state or not, head dims, step length; `GatedDeltaNet.recurrence_plan`),
never by a flag:

* "kernel": on a TPU, with per-slot state, head dims of whole 128-lane
  tiles and a step of at most `MAX_STEP_TOKENS` tokens, ONE Pallas call
  a layer holds a row's `S` in VMEM over the step's positions: read
  once, written once onto the donated input, and rows whose
  `row_tokens` is 0 neither read nor written (their `o` is 0);
* "plain": with per-slot state everywhere else, a `lax.scan` of
  `delta_rule_step` over the step's positions in plain jax.numpy, which
  reads and writes every slot's `S` a position, live or not;
* "chunked" / "chunked_kernel": the stateless shape: the recurrence a
  chunk of positions at a time, this op's one decay a head broadcast
  over the key's channels; the backward pass keeps the chunk-boundary
  states only, where the scan a position keeps every position's.
  "chunked" is plain jax.numpy that jax differentiates
  (`ops/chunked_delta_rule.py`: every backend but the TPU, and rows
  under a chunk or head dims that are no whole 128-lane tiles there);
  "chunked_kernel" is the same rule as Pallas kernels, forward and
  backward (`ops/pallas/chunked_delta_rule.py`).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..fftype import DataType, OperatorType
from ..initializer import (DEFAULT_WEIGHT_INIT, ConstantInitializer,
                           ZeroInitializer)
from ..obs.scopes import scope
from ..tensor import ParallelDim, ParallelTensorShape
from .chunked_delta_rule import l2norm, pick_chunk
from .op import Op, ShapeError, ShardConfig, WeightSpec, rstate_group
from .pallas.chunked_delta_rule import CHUNKED_RULES
from .pallas.gated_delta_rule import gated_delta_rule, pick_recurrence
from .short_conv import causal_depthwise_conv

_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class GatedDeltaNetParams:
    embed_dim: int
    num_k_heads: int
    num_v_heads: int
    head_k_dim: int
    head_v_dim: int
    conv_kernel: int = 4
    eps: float = 1e-6

    @property
    def key_dim(self) -> int:
        return self.num_k_heads * self.head_k_dim

    @property
    def value_dim(self) -> int:
        return self.num_v_heads * self.head_v_dim

    @property
    def conv_dim(self) -> int:
        """Channels the conv runs over: [q | k | v]."""
        return 2 * self.key_dim + self.value_dim


def delta_rule_step(S, q, k, v, g, beta):
    """One position of every row and value head, float32: S [b, h, dk,
    dv], q / k [b, h, dk], v [b, h, dv], beta [b, h], g [b, h] (one
    decay a head) or [b, h, dk] (one a channel of the key) -> (S, o)."""
    S = S * jnp.exp(g)[(..., None, None) if g.ndim == 2 else (..., None)]
    d = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", S, k,
                                          precision=_HIGHEST))
    S = S + k[..., :, None] * d[..., None, :]
    return S, jnp.einsum("bhkv,bhk->bhv", S, q, precision=_HIGHEST)


def delta_rule_scan(S, q, k, v, g, beta):
    """`delta_rule_step` over a step's positions in order, plain
    jax.numpy: S [b, h, dk, dv], q / k [b, s, h, dk], v [b, s, h, dv],
    beta [b, s, h], g [b, s, h] or [b, s, h, dk] -> (S, o [b, s, h, dv]).
    One position is the step itself, more a `lax.scan` of it."""
    if q.shape[1] == 1:
        S, o = delta_rule_step(S, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                               beta[:, 0])
        return S, o[:, None]
    S, o = jax.lax.scan(
        lambda S, xs: delta_rule_step(S, *xs), S,
        tuple(jnp.swapaxes(t, 0, 1) for t in (q, k, v, g, beta)))
    return S, jnp.swapaxes(o, 0, 1)


class GatedDeltaNet(Op):
    op_type = OperatorType.GATED_DELTA_NET
    float32_weights = ("A_log", "dt_bias", "rec_state")

    def __init__(self, params, inputs, name="", shard=None,
                 slot_state: bool = False):
        # must exist before Op.__init__ runs make_weight_specs
        self._slot_state = bool(slot_state)
        super().__init__(params, inputs, name=name,
                         shard=shard or ShardConfig())

    def ctor_kwargs(self) -> dict:
        return {"slot_state": True} if self._slot_state else {}

    def slot_state_entries(self):
        return ("conv_state", "rec_state") if self._slot_state else ()

    def recurrence_plan(self, step_tokens: int) -> str:
        """"kernel", "plain", "chunked" or "chunked_kernel": what a step
        of `step_tokens` tokens a row takes on this backend
        (`pick_recurrence`)."""
        p: GatedDeltaNetParams = self.params
        return pick_recurrence(jax.default_backend(), self._slot_state,
                               p.head_k_dim, p.head_v_dim, step_tokens)

    def infer_output_shapes(self, input_shapes):
        (x,) = input_shapes
        p: GatedDeltaNetParams = self.params
        xd = [d for d in x.dims if not d.is_replica_dim]
        if len(xd) != 3 or xd[2].size != p.embed_dim:
            raise ShapeError(f"{self.name}: expect [batch, seq, "
                             f"{p.embed_dim}], got {x.logical_shape}")
        if xd[1].degree != 1 or xd[2].degree != 1 \
                or not self.shard.is_trivial():
            raise ShapeError(
                f"{self.name}: the recurrence is sharded over the batch "
                "only (heads over a model axis are not built yet)")
        if p.num_v_heads % p.num_k_heads or p.conv_kernel < 2:
            raise ShapeError(
                f"{self.name}: {p.num_k_heads} key heads must divide "
                f"{p.num_v_heads} value heads, and the conv needs a "
                f"kernel of 2 or more (got {p.conv_kernel})")
        if self._slot_state and xd[0].degree != 1:
            raise ShapeError(f"{self.name}: per-slot state needs an "
                             "unsharded batch dim (slots are host-owned)")
        return [x]

    def num_trainable_weights(self) -> int:
        return 7

    def make_weight_specs(self, input_shapes):
        (x,) = input_shapes
        p: GatedDeltaNetParams = self.params
        slots = [d for d in x.dims if not d.is_replica_dim][0].size
        rep = ParallelDim(1, x.total_degree, is_replica_dim=True)

        def w(*sizes, dtype=x.dtype, replica=rep):
            return ParallelTensorShape(
                tuple(ParallelDim(s) for s in sizes) + (replica,), dtype)

        init, zero = DEFAULT_WEIGHT_INIT, ZeroInitializer()
        e, hv = p.embed_dim, p.num_v_heads
        specs = [
            WeightSpec("in_proj_qkvz", w(e, p.conv_dim + p.value_dim), init),
            WeightSpec("in_proj_ba", w(e, 2 * hv), init),
            WeightSpec("conv1d", w(p.conv_dim, p.conv_kernel), init),
            WeightSpec("dt_bias", w(hv, dtype=DataType.FLOAT),
                       ConstantInitializer(1.0)),
            WeightSpec("A_log", w(hv, dtype=DataType.FLOAT), zero),
            WeightSpec("norm", w(p.head_v_dim), ConstantInitializer(1.0)),
            WeightSpec("out_proj", w(p.value_dim, e), init),
        ]
        if not self._slot_state:
            return specs
        one = ParallelDim(1, 1, is_replica_dim=True)
        return specs + [
            WeightSpec("conv_state", w(slots, p.conv_kernel - 1, p.conv_dim,
                                       replica=one), zero),
            WeightSpec("rec_state",
                       w(slots, hv, p.head_k_dim, p.head_v_dim,
                         dtype=DataType.FLOAT, replica=one), zero),
            WeightSpec("row_tokens", w(slots, dtype=DataType.INT32,
                                       replica=one), zero),
        ]

    def forward(self, inputs, weights, *, training=False, rng=None):
        (x,) = inputs
        p: GatedDeltaNetParams = self.params
        w_qkvz, w_ba, conv_w, dt_bias, a_log, norm_w, w_out = weights[:7]
        b, s = x.shape[:2]
        hk, hv, dk, dv = (p.num_k_heads, p.num_v_heads, p.head_k_dim,
                          p.head_v_dim)
        f32 = jnp.float32
        with scope("proj"):
            mixed = jnp.matmul(x, w_qkvz)
            qkv, z = mixed[..., :p.conv_dim], mixed[..., p.conv_dim:]
            ba = jnp.matmul(x, w_ba, preferred_element_type=f32)
        with scope("conv"):
            if self._slot_state:
                tail, S, row_tokens = weights[7:]
                count = jnp.clip(row_tokens.reshape(b).astype(jnp.int32),
                                 0, s)
            else:
                tail = jnp.zeros((b, p.conv_kernel - 1, p.conv_dim),
                                 qkv.dtype)
                S = jnp.zeros((b, hv, dk, dv), f32)
                count = jnp.full((b,), s, jnp.int32)
            # causal depthwise conv over [the row's last K - 1 inputs |
            # the step's]: output t reads inputs t .. t + K - 1 of it
            window = jnp.concatenate([tail.astype(qkv.dtype), qkv], axis=1)
            conv = jax.nn.silu(causal_depthwise_conv(window, conv_w, s))
            # the window's K - 1 inputs that end at the row's last real one
            last = count[:, None] + jnp.arange(p.conv_kernel - 1,
                                               dtype=jnp.int32)
            new_tail = jnp.take_along_axis(window, last[..., None], axis=1)

        with scope("recurrence"):  # with what it is fed: q, k, v, g, beta
            q = conv[..., :p.key_dim].reshape(b, s, hk, dk)
            k = conv[..., p.key_dim:2 * p.key_dim].reshape(b, s, hk, dk)
            v = conv[..., 2 * p.key_dim:].reshape(b, s, hv, dv)
            plan = self.recurrence_plan(s)
            # (the chunked rules norm q and k themselves: per head, so
            # after the repeat of the key heads is the same)
            unit = plan not in CHUNKED_RULES
            q = jnp.repeat(l2norm(q) * dk ** -0.5 if unit else q, hv // hk,
                           axis=2)
            k = jnp.repeat(l2norm(k) if unit else k, hv // hk, axis=2)
            real = (jnp.arange(s, dtype=jnp.int32)[None, :]
                    < count[:, None])[..., None]  # [b, s, 1]
            beta = jnp.where(real, jax.nn.sigmoid(ba[..., :hv]), 0.0)
            g = jnp.where(
                real, -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
                    ba[..., hv:] + dt_bias.astype(f32)), 0.0)
            S = S.astype(f32)
            if plan == "kernel":
                S, o = gated_delta_rule(S, q, k, v, g, beta, count)
            elif plan in CHUNKED_RULES:
                # the table's one layout: a head a block of channels of
                # a flat tensor, a decay a channel
                S, o = CHUNKED_RULES[plan](
                    S, *(t.reshape(b, s, -1) for t in (q, k, v)),
                    jnp.repeat(g, dk, axis=2), beta, *pick_chunk(s),
                    operand_dtype=x.dtype)
                o = o.reshape(b, s, hv, dv)
            else:
                S, o = delta_rule_scan(S, q, k, v, g, beta)  # [b, s, hv, dv]
        with scope("out"):
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1,
                                           keepdims=True) + p.eps)
            y = (o * norm_w.astype(f32)
                 * jax.nn.silu(z.astype(f32).reshape(b, s, hv, dv)))
            out = jnp.matmul(y.reshape(b, s, p.value_dim).astype(x.dtype),
                             w_out)
            out = out.astype(x.dtype)
        if not self._slot_state:
            return [out]
        return [out, new_tail, S, row_tokens]

    def flops(self):
        """The four products, the conv, and the recurrence: a position
        of a value head decays S, reads it twice (`S^T k`, `S^T q`) and
        adds an outer product: 7 operations an element of S."""
        p: GatedDeltaNetParams = self.params
        b, s, e = self.inputs[0].shape.logical_shape
        proj = 2.0 * e * (p.conv_dim + 2 * p.value_dim + 2 * p.num_v_heads)
        conv = 2.0 * p.conv_dim * p.conv_kernel
        rec = 7.0 * p.num_v_heads * p.head_k_dim * p.head_v_dim
        return b * s * (proj + conv + rec)

    def dispatch_group(self):
        return "rstate" if self._slot_state else None

    @classmethod
    def dispatch_group_of(cls, ops, *, batch_slots, prefill_chunk,
                          state_bytes, **twin):
        """The recurrent state: `rstate_rows_live`, the rows a dispatch
        had to advance, against `rstate_rows_touched`, the rows whose
        state the program of that step length read and wrote: the
        advanced rows where every layer of it takes the kernel
        (`recurrence_plan`, asked once for each step length the twin
        runs), every slot under the plain recurrence.  `gdn_kernel_ops`
        layers take the kernel in every step program, `gdn_plain_ops`
        the plain scan in some."""
        return rstate_group(
            ops, lambda op, s: op.recurrence_plan(s) == "kernel", "gdn",
            batch_slots=batch_slots, prefill_chunk=prefill_chunk,
            state_bytes=state_bytes)
