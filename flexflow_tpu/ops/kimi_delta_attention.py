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
op of its own that shares the recurrence (`ops/chunked_delta_rule.py`),
the conv (`short_conv.causal_depthwise_conv`) and `l2norm` with it.

Stateless only: every row starts from zero and runs its whole `[b, s]`
input, which is what a trainer runs.  `recurrence_plan` asks
`pick_recurrence` as `GatedDeltaNet` does and gets a chunk of positions
at a time: "chunked_kernel" (`ops/pallas/chunked_delta_rule.py`, the
rule as Pallas kernels, forward and backward) on a TPU with 128-lane
heads and a row of a full chunk or more, "chunked" (plain jax.numpy)
everywhere else; the chunk is `pick_chunk`'s.  The serving twin (a conv
tail a
projection and `S` a slot, a per-channel decay in
`ops/pallas/gated_delta_rule.py`'s kernel) is not built (ROADMAP).

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
from .gated_delta_net import delta_rule_scan, l2norm
from .op import Op, ShapeError, ShardConfig, WeightSpec, remat_keep
from .pallas.chunked_delta_rule import CHUNKED_RULES
from .pallas.gated_delta_rule import pick_recurrence
from .short_conv import causal_depthwise_conv


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
        """Positions a chunk of the core holds; 0 for the scan a
        position."""
        if self.recurrence_plan(step_tokens) not in CHUNKED_RULES:
            return 0
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
        with scope("conv"):
            def conv(t, taps):
                window = jnp.concatenate(
                    [jnp.zeros((b, p.conv_kernel - 1, p.proj_dim), t.dtype),
                     t], axis=1)
                return jax.nn.silu(causal_depthwise_conv(window, taps, s)) \
                    .reshape(b, s, h, d)

            q, k, v = map(conv, mixed, (cq, ck, cv))
            q, k = l2norm(q) * d ** -0.5, l2norm(k)
        with scope("gate"):  # what the core is fed beside q, k, v
            decay_in = jnp.matmul(jnp.matmul(x, w_fa), w_fb,
                                  preferred_element_type=f32)
            g = -jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(
                (decay_in + dt_bias.astype(f32)).reshape(b, s, h, d))
            beta = jax.nn.sigmoid(jnp.matmul(x, w_b,
                                             preferred_element_type=f32))
        with scope("core"):
            S = jnp.zeros((b, h, d, d), f32)
            rule = CHUNKED_RULES.get(self.recurrence_plan(s))
            if rule is not None:
                _, o = rule(S, q, k, v, g, beta, *pick_chunk(s),
                            operand_dtype=x.dtype)
            else:
                _, o = delta_rule_scan(S, q, k, v, g, beta)
            o = remat_keep(o.astype(x.dtype))  # [b, s, h, d]
        with scope("norm_gate"):
            gate = jnp.matmul(jnp.matmul(x, w_ga), w_gb,
                              preferred_element_type=f32)
            o = o.astype(f32)
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1,
                                           keepdims=True) + p.eps)
            y = (o * norm_w.astype(f32)
                 * jax.nn.sigmoid(gate.reshape(b, s, h, d)))
        with scope("out"):
            # y is written once, in the compute precision: left to fuse,
            # the head norm and the gate (and the core's layout change
            # before them) are recomputed for every tile of the product
            # (4.7 ms a layer against 0.95 on the v5e: PERF.md section
            # 6, PR 43)
            y = jax.lax.optimization_barrier(
                y.reshape(b, s, p.proj_dim).astype(x.dtype))
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
