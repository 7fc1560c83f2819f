"""Kimi Delta Attention (KDA): the delta rule with a decay per CHANNEL
of the key, the linear-attention mixer of three layers in four of the
`kimi_linear` family.

    q~ = silu(conv(x W_q));  k~ = silu(conv(x W_k));  v = silu(conv(x W_v))
         (three causal depthwise convs over time, K taps a channel)
    q = l2norm(q~_h) / sqrt(d_k);   k = l2norm(k~_h)         per head h
    g = -exp(A_log_h) * softplus(((x W_fa) W_fb + dt_bias)_h)   in R^d_k
    beta = sigmoid(x w_b)_h
    per head, S a [d_k, d_v] matrix, for each position in order:
        S' = Diag(exp(g_t)) S;  d = beta_t (v_t - S'^T k_t)
        S = S' + k_t d^T;       o_t = S^T q_t
    y = w_n * rmsnorm(o) * sigmoid(((x W_ga) W_gb)_h);   out = concat_h(y) W_o

`GatedDeltaNet` (ops/gated_delta_net.py) is the case of a `g` that is
the same over a head's channels; its one fused `[q | k | v | z]`
projection, one conv over `[q | k | v]`, key heads repeated to value
heads and silu output gate are another layer's weights, so this is an
op of its own that shares the recurrence (`ops/chunked_delta_rule.py`
and its kernels, `l2norm`'s formula with them) and the conv
(`short_conv.causal_depthwise_conv`) with it.

Stateless only: every row starts from zero and runs its whole `[b, s]`
input, which is what a trainer runs.  `recurrence_plan` asks
`pick_recurrence` as `GatedDeltaNet` does and gets a chunk of positions
at a time: "chunked_kernel" (`ops/pallas/chunked_delta_rule.py`, the
rule as Pallas kernels, forward and backward) on a TPU with 128-lane
heads and a row of a full chunk or more, "chunked" (plain jax.numpy)
everywhere else; the chunk is `pick_chunk`'s.  The serving twin (a conv
tail a projection and `S` a slot, a per-channel decay in
`ops/pallas/gated_delta_rule.py`'s kernel) is not built (ROADMAP).

Layout: a head is a column block of d channels.  The convs write q~,
k~, v flat, `[b, s, h d]`, the decay `g` is formed flat (`A_log`
repeated over a head's channels), and the op hands them to the rule AS
THEY ARE: the l2norm of q~ and k~ above runs where the rule's operands
are formed, in the operands' kernels on a head's resident `[C, d]` tile
on the kernel plan, in jax.numpy on the other (`CHUNKED_RULES`, one
signature, one layout).  The rule returns `o` as v came, flat, and the
head norm of the last line is `head_rms`: the heads' sums of squares
and the spread of their rsqrt are two small products with a 0/1
membership matrix.  So the op never forms
`[b, s, h, d]`, of its operands, of `o` or of the gate: on the v5e that
reshape of a float32 tensor is a copy (the tile is the last two dims),
~20 ms a step with the norms' own passes beside it (PERF.md section 6,
PR 45).

Under `remat` a checkpointed segment keeps this op's matrix products
by the executor's policy (they have no batch dimension) and the core's
output, which the op tags (`remat_keep`): the gated norm and the output
projection's backward pass read `o` without waiting for the scan over
chunks to run again.
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
from .chunked_delta_rule import pick_chunk
from .op import Op, ShapeError, ShardConfig, WeightSpec, remat_keep
from .pallas.chunked_delta_rule import CHUNKED_RULES
from .pallas.gated_delta_rule import pick_recurrence
from .short_conv import causal_depthwise_conv


_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class KimiDeltaAttentionParams:
    embed_dim: int
    num_heads: int
    head_dim: int  # d_k = d_v
    conv_kernel: int = 4
    eps: float = 1e-5

    @property
    def proj_dim(self) -> int:
        return self.num_heads * self.head_dim


def head_rms(o, num_heads: int, eps: float):
    """o [b, s, h d] float32, a head a block of d channels -> each head
    over the root mean square of its channels, with no `[b, s, h, d]`
    form (a copy on the chip): the heads' sums of squares are a product
    with the 0/1 membership matrix `[h d, h]`, and its transpose spreads
    the rsqrt back over a head's channels; float32 products at
    `HIGHEST`, so a sum is float32's and the spread is exact.  The
    spread carries the row as a batch dimension: the same program on
    the chip, and the `products` level of the executor's `remat` (which
    keeps what has none) then recomputes its `[b, s, h d]` result from
    the `[b, s, h]` sums it does keep (`jnp.repeat` would do too, and
    brings the by-head copy back: PERF.md section 6, PR 45)."""
    d = o.shape[-1] // num_heads
    member = (jnp.arange(o.shape[-1])[:, None] // d
              == jnp.arange(num_heads)).astype(o.dtype)
    mean = jnp.matmul(o * o, member, precision=_HIGHEST) / d
    return o * jnp.einsum(
        "bsh,bhc->bsc", jax.lax.rsqrt(mean + eps),
        jnp.broadcast_to(member.T, o.shape[:1] + member.T.shape),
        precision=_HIGHEST)


class KimiDeltaAttention(Op):
    op_type = OperatorType.KIMI_DELTA_ATTENTION
    float32_weights = ("A_log", "dt_bias")

    def __init__(self, params, inputs, name="", shard=None):
        super().__init__(params, inputs, name=name,
                         shard=shard or ShardConfig())

    def recurrence_plan(self, step_tokens: int) -> str:
        """What a step of `step_tokens` tokens a row takes
        (`pick_recurrence`; this op has no per-slot state)."""
        p: KimiDeltaAttentionParams = self.params
        return pick_recurrence(jax.default_backend(), False, p.head_dim,
                               p.head_dim, step_tokens)

    def chunk_tokens(self, step_tokens: int) -> int:
        """Positions a chunk of the core holds (both plans run a chunk
        at a time)."""
        return pick_chunk(step_tokens)[0]

    def infer_output_shapes(self, input_shapes):
        (x,) = input_shapes
        p: KimiDeltaAttentionParams = self.params
        xd = [d for d in x.dims if not d.is_replica_dim]
        if len(xd) != 3 or xd[2].size != p.embed_dim:
            raise ShapeError(f"{self.name}: expect [batch, seq, "
                             f"{p.embed_dim}], got {x.logical_shape}")
        if xd[1].degree != 1 or xd[2].degree != 1 \
                or not self.shard.is_trivial():
            raise ShapeError(
                f"{self.name}: the recurrence is sharded over the batch "
                "only (heads over a model axis are not built yet)")
        if p.conv_kernel < 2:
            raise ShapeError(f"{self.name}: the conv needs a kernel of 2 "
                             f"or more (got {p.conv_kernel})")
        return [x]

    def make_weight_specs(self, input_shapes):
        (x,) = input_shapes
        p: KimiDeltaAttentionParams = self.params
        rep = ParallelDim(1, x.total_degree, is_replica_dim=True)

        def w(*sizes, dtype=x.dtype):
            return ParallelTensorShape(
                tuple(ParallelDim(s) for s in sizes) + (rep,), dtype)

        init, one = DEFAULT_WEIGHT_INIT, ConstantInitializer(1.0)
        e, h, d, hd = p.embed_dim, p.num_heads, p.head_dim, p.proj_dim
        return [
            WeightSpec("q_proj", w(e, hd), init),
            WeightSpec("k_proj", w(e, hd), init),
            WeightSpec("v_proj", w(e, hd), init),
            WeightSpec("q_conv", w(hd, p.conv_kernel), init),
            WeightSpec("k_conv", w(hd, p.conv_kernel), init),
            WeightSpec("v_conv", w(hd, p.conv_kernel), init),
            WeightSpec("f_a_proj", w(e, d), init),
            WeightSpec("f_b_proj", w(d, hd), init),
            WeightSpec("dt_bias", w(hd, dtype=DataType.FLOAT), one),
            WeightSpec("A_log", w(h, dtype=DataType.FLOAT),
                       ZeroInitializer()),
            WeightSpec("b_proj", w(e, h), init),
            WeightSpec("g_a_proj", w(e, d), init),
            WeightSpec("g_b_proj", w(d, hd), init),
            WeightSpec("o_norm", w(d), one),
            WeightSpec("o_proj", w(hd, e), init),
        ]

    def forward(self, inputs, weights, *, training=False, rng=None):
        (x,) = inputs
        p: KimiDeltaAttentionParams = self.params
        (w_q, w_k, w_v, cq, ck, cv, w_fa, w_fb, dt_bias, a_log, w_b, w_ga,
         w_gb, norm_w, w_o) = weights
        b, s = x.shape[:2]
        h, d = p.num_heads, p.head_dim
        f32 = jnp.float32
        with scope("proj"):
            mixed = [jnp.matmul(x, w) for w in (w_q, w_k, w_v)]
        with scope("conv"):  # q~, k~, v: flat, [b, s, h d] float32
            def conv(t, taps):
                window = jnp.concatenate(
                    [jnp.zeros((b, p.conv_kernel - 1, p.proj_dim), t.dtype),
                     t], axis=1)
                return jax.nn.silu(causal_depthwise_conv(window, taps, s))

            q, k, v = map(conv, mixed, (cq, ck, cv))
        with scope("gate"):  # what the core is fed beside them, flat too
            decay_in = jnp.matmul(jnp.matmul(x, w_fa), w_fb,
                                  preferred_element_type=f32)
            g = -jnp.repeat(jnp.exp(a_log.astype(f32)), d) \
                * jax.nn.softplus(decay_in + dt_bias.astype(f32))
            beta = jax.nn.sigmoid(jnp.matmul(x, w_b,
                                             preferred_element_type=f32))
        with scope("core"):  # with the l2norm of q~ and k~, a head's
            rule = CHUNKED_RULES[self.recurrence_plan(s)]
            _, o = rule(jnp.zeros((b, h, d, d), f32), q, k, v, g, beta,
                        *pick_chunk(s), operand_dtype=x.dtype)
            o = remat_keep(o.astype(x.dtype))  # [b, s, h d], as v came
        with scope("norm_gate"):
            gate = jnp.matmul(jnp.matmul(x, w_ga), w_gb,
                              preferred_element_type=f32)
            y = (head_rms(o.astype(f32), h, p.eps)
                 * jnp.tile(norm_w.astype(f32), h) * jax.nn.sigmoid(gate))
        with scope("out"):
            # y is written once, in the compute precision: left to fuse,
            # the head norm and the gate are recomputed for every tile
            # of the product (4.7 ms a layer against 0.95 on the v5e:
            # PERF.md section 6, PR 43)
            y = jax.lax.optimization_barrier(y.astype(x.dtype))
            return [jnp.matmul(y, w_o).astype(x.dtype)]

    def flops(self):
        """The products, the three convs, and the recurrence: a position
        of a head decays S, reads it twice (`S^T k`, `S^T q`) and adds
        an outer product, 7 operations an element of S, as
        `GatedDeltaNet.flops` counts them."""
        p: KimiDeltaAttentionParams = self.params
        b, s, e = self.inputs[0].shape.logical_shape
        hd, d = p.proj_dim, p.head_dim
        proj = 2.0 * (4 * e * hd + 2 * (e * d + d * hd) + e * p.num_heads)
        conv = 2.0 * 3 * hd * p.conv_kernel
        rec = 7.0 * p.num_heads * d * d
        return b * s * (proj + conv + rec)
