"""A gated short convolution: the mixer that stands where attention
does in most layers of the `lfm2_moe` family.

    [B | C | x] = h W_in                       W_in [e, 3e], no bias
    y_t = C_t * sum_{j < K} w[:, j] * (B x)_{t - (K - 1) + j}
    out = y W_out

The convolution is causal and depthwise over the e channels (every
channel has its own K taps), sees zeros before the sequence, and has
neither a bias nor an activation; the two gates multiply elementwise.
Stateless: every row runs its whole `[b, s]` input from zeros, which
is what a trainer runs.  A serving twin would carry each row's last
K - 1 gated inputs as `Op.slot_state_entries` (ROADMAP R4c); it is not
built.

`causal_depthwise_conv` is the one convolution of this kind in the
package: `GatedDeltaNet` runs it over [q | k | v] from each row's tail.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from ..fftype import OperatorType
from ..initializer import DEFAULT_WEIGHT_INIT
from ..obs.scopes import scope
from ..tensor import ParallelDim, ParallelTensorShape
from .op import Op, ShapeError, WeightSpec


def causal_depthwise_conv(window, taps, s: int):
    """window [b, K - 1 + s, c]: each row's K - 1 inputs before the
    step, then the step's s; taps [c, K].  Output t reads inputs
    t .. t + K - 1 of the window: [b, s, c] in float32."""
    f32 = jnp.float32
    return sum(window[:, i:i + s].astype(f32) * taps[:, i].astype(f32)
               for i in range(taps.shape[1]))


@dataclasses.dataclass(frozen=True)
class ShortConvParams:
    embed_dim: int
    kernel: int = 3  # the published `conv_L_cache`


class ShortConv(Op):
    op_type = OperatorType.SHORT_CONV

    def infer_output_shapes(self, input_shapes):
        (x,) = input_shapes
        p: ShortConvParams = self.params
        xd = [d for d in x.dims if not d.is_replica_dim]
        if len(xd) != 3 or xd[2].size != p.embed_dim:
            raise ShapeError(f"{self.name}: expect [batch, seq, "
                             f"{p.embed_dim}], got {x.logical_shape}")
        if xd[1].degree != 1 or xd[2].degree != 1 \
                or not self.shard.is_trivial():
            raise ShapeError(f"{self.name}: the convolution runs along an "
                             "unsharded sequence (shard its batch)")
        if p.kernel < 2:
            raise ShapeError(f"{self.name}: a kernel of {p.kernel} tap is "
                             "no convolution")
        return [x]

    def make_weight_specs(self, input_shapes):
        (x,) = input_shapes
        p: ShortConvParams = self.params
        rep = ParallelDim(1, x.total_degree, is_replica_dim=True)

        def w(*sizes):
            return ParallelTensorShape(
                tuple(ParallelDim(s) for s in sizes) + (rep,), x.dtype)

        e = p.embed_dim
        return [WeightSpec("in_proj", w(e, 3 * e), DEFAULT_WEIGHT_INIT),
                WeightSpec("conv", w(e, p.kernel), DEFAULT_WEIGHT_INIT),
                WeightSpec("out_proj", w(e, e), DEFAULT_WEIGHT_INIT)]

    def forward(self, inputs, weights, *, training=False, rng=None):
        (x,) = inputs
        p: ShortConvParams = self.params
        w_in, taps, w_out = weights
        b, s, e = x.shape
        with scope("proj"):
            bcx = jnp.matmul(x, w_in)
            gate_b, gate_c, xs = (bcx[..., :e], bcx[..., e:2 * e],
                                  bcx[..., 2 * e:])
            bx = gate_b * xs
        with scope("conv"):
            window = jnp.concatenate(
                [jnp.zeros((b, p.kernel - 1, e), bx.dtype), bx], axis=1)
            y = gate_c * causal_depthwise_conv(window, taps, s).astype(
                x.dtype)
        with scope("out"):
            return [jnp.matmul(y, w_out)]

    def flops(self):
        p: ShortConvParams = self.params
        b, s, e = self.inputs[0].shape.logical_shape
        return b * s * e * (8.0 * e + 2.0 * p.kernel + 2.0)
