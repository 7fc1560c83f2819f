"""One chip's share of a routed-expert layer, with its shared expert.

The layer is TOLD WHICH EXPERTS IT HOLDS: `experts_total` is the
router's published width, `experts_held` of them live here, starting
at `first_held`.  It routes every token over all `experts_total` in
float32 (scores by the params' `scoring` rule: `sigmoid` of each logit,
or `softmax` over all the logits; the top `top_k` of score + bias, the
chosen scores normalised over ALL of the chosen and scaled), and adds

    sum over experts chosen AND held here of w_e E_e(h)  +  g E_shared(h)

with `g = 1`, or `sigmoid(w_sg . h)` where `shared_expert_gate` is set.

Experts that live on other chips add nothing: on one chip the layer
runs without its exchange, and nothing stands in for the absent chips.
`experts_held == experts_total` is the whole layer.

No token is dropped at any load.  Every held expert is applied to every
row of the step and the result combined with the routing weights (zero
where an expert was not chosen): a capacity of the step's rows, which
at a decode step's few rows costs what any static-shape dispatch costs,
the read of the held experts' weights.  `moe_stats` counts what a
dispatch with a smaller capacity would have to get right: routed pairs
that landed on held experts, pairs the combine left out (0), the rows
of the fullest held expert, and the held experts that received a row.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..fftype import DataType, OperatorType
from ..initializer import DEFAULT_WEIGHT_INIT, ZeroInitializer
from ..tensor import ParallelDim, ParallelTensorShape
from .dense import gated_mlp
from .op import Op, ShapeError, WeightSpec

#: order of the counters in the `moe_stats` state entry
MOE_STATS = ("pairs", "dropped", "max_rows", "hit")


@dataclasses.dataclass(frozen=True)
class RoutedExpertsParams:
    experts_total: int
    experts_held: int
    first_held: int
    top_k: int
    expert_hidden: int
    shared_hidden: int = 0  # 0: no shared expert
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    dtype: DataType = DataType.FLOAT
    scoring: str = "sigmoid"  # or "softmax", over all experts_total
    shared_expert_gate: bool = False  # shared expert times sigmoid(w . h)


def route(h, router, bias, p: RoutedExpertsParams):
    """h [t, e] -> (chosen expert ids [t, k], their weights [t, k]),
    all in float32 at full matmul precision: scores by `p.scoring`, the
    bias only chooses, the normaliser runs over all k chosen."""
    logits = jnp.matmul(
        h.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)
    scores = (jax.nn.softmax(logits, axis=-1) if p.scoring == "softmax"
              else jax.nn.sigmoid(logits))
    _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), p.top_k)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if p.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * p.routed_scaling_factor


class RoutedExperts(Op):
    op_type = OperatorType.ROUTED_EXPERTS
    float32_weights = ("router", "router_bias")
    has_aux_state = True  # weights[num_trainable_weights():] are state

    def infer_output_shapes(self, input_shapes):
        (ishape,) = input_shapes
        p: RoutedExpertsParams = self.params
        last = [d for d in ishape.dims if not d.is_replica_dim][-1]
        if last.degree != 1 or not self.shard.is_trivial():
            raise ShapeError(
                f"{self.name}: this layer holds one chip's experts; "
                "experts spread over a mesh axis are not built yet")
        if not (0 <= p.first_held
                and p.first_held + p.experts_held <= p.experts_total
                and 1 <= p.top_k <= p.experts_total):
            raise ShapeError(
                f"{self.name}: held experts [{p.first_held}, "
                f"{p.first_held + p.experts_held}) and top_k {p.top_k} "
                f"do not fit {p.experts_total} experts")
        if p.scoring not in ("sigmoid", "softmax"):
            raise ShapeError(f"{self.name}: scoring {p.scoring!r} is not "
                             "'sigmoid' or 'softmax'")
        if p.shared_expert_gate and not p.shared_hidden:
            raise ShapeError(f"{self.name}: shared_expert_gate without a "
                             "shared expert")
        return [ParallelTensorShape(ishape.dims, p.dtype)]

    def num_trainable_weights(self) -> int:
        p: RoutedExpertsParams = self.params
        return 5 + (3 if p.shared_hidden else 0) + int(p.shared_expert_gate)

    def make_weight_specs(self, input_shapes):
        (ishape,) = input_shapes
        p: RoutedExpertsParams = self.params
        e = ishape.logical_shape[-1]
        rep = ParallelDim(1, ishape.total_degree, is_replica_dim=True)

        def w(*sizes, dtype=p.dtype):
            return ParallelTensorShape(
                tuple(ParallelDim(s) for s in sizes) + (rep,), dtype)

        init, zero = DEFAULT_WEIGHT_INIT, ZeroInitializer()
        n, f = p.experts_held, p.expert_hidden
        specs = [
            WeightSpec("router", w(e, p.experts_total), init),
            WeightSpec("router_bias", w(p.experts_total), zero),
            WeightSpec("w_gate", w(n, e, f), init),
            WeightSpec("w_up", w(n, e, f), init),
            WeightSpec("w_down", w(n, f, e), init),
        ]
        if p.shared_hidden:
            specs += [
                WeightSpec("shared_gate", w(e, p.shared_hidden), init),
                WeightSpec("shared_up", w(e, p.shared_hidden), init),
                WeightSpec("shared_down", w(p.shared_hidden, e), init),
            ]
        if p.shared_expert_gate:
            specs.append(WeightSpec("shared_expert_gate", w(e), init))
        return specs + [WeightSpec(
            "moe_stats", w(len(MOE_STATS), dtype=DataType.INT32), zero)]

    def forward(self, inputs, weights, *, training=False, rng=None):
        (x,) = inputs
        p: RoutedExpertsParams = self.params
        router, bias, w_gate, w_up, w_down = weights[:5]
        h = x.reshape(-1, x.shape[-1])
        chosen, w = route(h, router, bias, p)
        # [t, k, held]: which held expert each chosen pair landed on;
        # a pair for an expert that lives elsewhere is all zeros
        landed = jax.nn.one_hot(chosen - p.first_held, p.experts_held,
                                dtype=jnp.float32)
        combine = jnp.einsum("tkx,tk->tx", landed, w)
        gate = jnp.einsum("te,xef->xtf", h, w_gate)
        up = jnp.einsum("te,xef->xtf", h, w_up)
        y = jnp.einsum("xtf,xfe->xte", jax.nn.silu(gate) * up, w_down)
        out = jnp.einsum("xte,tx->te", y, combine.astype(y.dtype))
        if p.shared_expert_gate:
            g = jax.nn.sigmoid(jnp.einsum(
                "te,e->t", h, weights[8],
                preferred_element_type=jnp.float32))
            out = out + gated_mlp(h, *weights[5:8]) * g[:, None].astype(
                out.dtype)
        elif p.shared_hidden:
            out = out + gated_mlp(h, *weights[5:8])
        rows = jnp.sum(landed, axis=(0, 1)).astype(jnp.int32)  # [held]
        pairs = jnp.sum(rows)
        stats = jnp.stack([
            pairs,
            pairs - jnp.sum(combine != 0).astype(jnp.int32),
            jnp.max(rows),
            jnp.sum(rows > 0).astype(jnp.int32),
        ])
        return [out.reshape(x.shape).astype(x.dtype), stats]

    def flops(self):
        """The router's product (either scoring rule is a few
        operations a logit on top), every held expert over every row,
        the shared expert and its gate's dot product."""
        p: RoutedExpertsParams = self.params
        t = self.inputs[0].shape.num_elements()  # rows x e
        return t * (2.0 * p.experts_total
                    + 6.0 * p.experts_held * p.expert_hidden
                    + 6.0 * p.shared_hidden
                    + 2.0 * int(p.shared_expert_gate))
