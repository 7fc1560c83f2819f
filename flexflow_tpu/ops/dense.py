"""Dense compute ops: Linear, Conv2D, Pool2D, Embedding, BatchMatmul.

Reference counterparts: src/ops/linear.cc (cublasGemmEx kernels,
kernels/linear_kernels.cu:213), src/ops/conv_2d.cc (cuDNN conv),
src/ops/pool_2d.cc, src/ops/embedding.cc (custom CUDA lookup,
attribute-parallel over vocab at embedding.cc:132-196),
src/ops/batch_matmul.cc (strided-batched GEMM, seq-length-dim support at
batch_matmul.cc:70-77).

TPU-first: all map onto `lax.dot_general` / `lax.conv_general_dilated` /
`lax.reduce_window` so XLA tiles them straight onto the MXU; backward is
autodiff.  Parallelism via ShardConfig:
  - Linear.channel  = out-channel partition (the reference's
    create_partition_linear_combine substitution);
  - Linear via partitioned in-dim = partial-sum output with replica
    degree = in-degree (the reference's Reduction-consumed output);
  - Embedding.attribute = vocab partition (attribute parallelism) —
    out-of-shard ids contribute zero and the partial outputs sum.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..fftype import ActiMode, AggrMode, DataType, OperatorType
from ..initializer import DEFAULT_BIAS_INIT, DEFAULT_WEIGHT_INIT
from ..tensor import ParallelDim, ParallelTensorShape
from .op import Op, ShapeError, ShardConfig, WeightSpec


def apply_activation(x: jax.Array, act: ActiMode) -> jax.Array:
    if act == ActiMode.NONE:
        return x
    if act == ActiMode.RELU:
        return jax.nn.relu(x)
    if act == ActiMode.SIGMOID:
        return jax.nn.sigmoid(x)
    if act == ActiMode.TANH:
        return jnp.tanh(x)
    if act == ActiMode.GELU:
        return jax.nn.gelu(x)
    raise ValueError(act)


@dataclasses.dataclass(frozen=True)
class LinearParams:
    out_channels: int
    use_bias: bool = True
    activation: ActiMode = ActiMode.NONE
    dtype: DataType = DataType.FLOAT


class Linear(Op):
    """`tied_to` names an Embedding whose `[entries, channels]` table
    this op reads as its kernel, transposed: `entries` = `out_channels`.
    The op then owns no weight (`borrowed_weights`): the table is one
    leaf of the weights tree, under the embedding's name."""

    op_type = OperatorType.LINEAR

    def __init__(self, params, inputs, name="", shard=ShardConfig(),
                 tied_to: str = ""):
        # must exist before Op.__init__ runs make_weight_specs
        self._tied_to = tied_to
        super().__init__(params, inputs, name=name, shard=shard)

    def ctor_kwargs(self) -> dict:
        return {"tied_to": self._tied_to} if self._tied_to else {}

    def borrowed_weights(self):
        return ((self._tied_to, "weight"),) if self._tied_to else ()

    def infer_output_shapes(self, input_shapes):
        (ishape,) = input_shapes
        p: LinearParams = self.params
        if self._tied_to and (p.use_bias or not self.shard.is_trivial()):
            raise ShapeError(
                f"{self.name}: a kernel tied to {self._tied_to}'s table "
                "has no bias and is sharded as the table is, not inside")
        dims = list(ishape.dims)
        data_dims = [d for d in dims if not d.is_replica_dim]
        in_dim = data_dims[-1]
        ri = ishape.replica_degree
        c = self.shard.channel
        if c > 1 and ri % c == 0:
            ri //= c  # replicated input consumed by channel shards
        out_replica = ri * in_dim.degree  # in-degree partials
        out_dims = tuple(
            d for d in data_dims[:-1]
        ) + (
            ParallelDim(p.out_channels, c),
            ParallelDim(1, out_replica, is_replica_dim=True),
        )
        return [ParallelTensorShape(out_dims, p.dtype)]

    def make_weight_specs(self, input_shapes):
        (ishape,) = input_shapes
        p: LinearParams = self.params
        data_dims = [d for d in ishape.dims if not d.is_replica_dim]
        in_dim = data_dims[-1]
        if self._tied_to:
            return []
        batch_degree = 1
        for d in data_dims[:-1]:
            batch_degree *= d.degree
        kernel = ParallelTensorShape(
            (
                ParallelDim(in_dim.size, in_dim.degree),
                ParallelDim(p.out_channels, self.shard.channel),
                ParallelDim(1, batch_degree, is_replica_dim=True),
            ),
            p.dtype,
        )
        specs = [WeightSpec("kernel", kernel, DEFAULT_WEIGHT_INIT)]
        if p.use_bias:
            bias = ParallelTensorShape(
                (
                    ParallelDim(p.out_channels, self.shard.channel),
                    ParallelDim(1, batch_degree * in_dim.degree, is_replica_dim=True),
                ),
                p.dtype,
            )
            specs.append(WeightSpec("bias", bias, DEFAULT_BIAS_INIT))
        return specs

    def forward(self, inputs, weights, *, training=False, rng=None):
        (x,) = inputs
        p: LinearParams = self.params
        if self._tied_to:  # the table, [out_channels, in]
            return [jnp.einsum("...e,ve->...v", x, weights[0])]
        kernel = weights[0]
        y = jnp.matmul(x, kernel)
        if p.use_bias:
            y = y + weights[1]
        return [apply_activation(y, p.activation)]

    def flops(self):
        ishape = self.inputs[0].shape
        return 2.0 * ishape.num_elements() * self.params.out_channels


@dataclasses.dataclass(frozen=True)
class Conv2DParams:
    out_channels: int
    kernel: Tuple[int, int]
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)
    groups: int = 1
    use_bias: bool = True
    activation: ActiMode = ActiMode.NONE
    dtype: DataType = DataType.FLOAT


class Conv2D(Op):
    """NCHW conv (reference convention, conv_2d.cc)."""

    op_type = OperatorType.CONV2D

    def _out_hw(self, h, w):
        p: Conv2DParams = self.params
        oh = (h + 2 * p.padding[0] - p.kernel[0]) // p.stride[0] + 1
        ow = (w + 2 * p.padding[1] - p.kernel[1]) // p.stride[1] + 1
        return oh, ow

    def infer_output_shapes(self, input_shapes):
        (ishape,) = input_shapes
        p: Conv2DParams = self.params
        n, cin, h, w = [d for d in ishape.dims if not d.is_replica_dim]
        if cin.size % p.groups != 0 or p.out_channels % p.groups != 0:
            raise ShapeError(f"{self.name}: groups {p.groups} mismatch")
        oh, ow = self._out_hw(h.size, w.size)
        if oh <= 0 or ow <= 0:
            raise ShapeError(f"{self.name}: non-positive output spatial dims")
        out_replica = ishape.replica_degree * cin.degree
        dims = (
            ParallelDim(n.size, n.degree),
            ParallelDim(p.out_channels, self.shard.channel),
            ParallelDim(oh, h.degree),
            ParallelDim(ow, w.degree),
            ParallelDim(1, out_replica, is_replica_dim=True),
        )
        return [ParallelTensorShape(dims, p.dtype)]

    def make_weight_specs(self, input_shapes):
        (ishape,) = input_shapes
        p: Conv2DParams = self.params
        n, cin, h, w = [d for d in ishape.dims if not d.is_replica_dim]
        # OIHW filter layout
        kernel = ParallelTensorShape(
            (
                ParallelDim(p.out_channels, self.shard.channel),
                ParallelDim(cin.size // p.groups, cin.degree),
                ParallelDim(p.kernel[0]),
                ParallelDim(p.kernel[1]),
                ParallelDim(1, n.degree * h.degree * w.degree, is_replica_dim=True),
            ),
            p.dtype,
        )
        specs = [WeightSpec("kernel", kernel, DEFAULT_WEIGHT_INIT)]
        if p.use_bias:
            bias = ParallelTensorShape(
                (
                    ParallelDim(p.out_channels, self.shard.channel),
                    ParallelDim(1, n.degree * h.degree * w.degree * cin.degree,
                                is_replica_dim=True),
                ),
                p.dtype,
            )
            specs.append(WeightSpec("bias", bias, DEFAULT_BIAS_INIT))
        return specs

    def forward(self, inputs, weights, *, training=False, rng=None):
        (x,) = inputs
        p: Conv2DParams = self.params
        # physical layout assigned by pcg/layout.py: NHWC puts channels
        # on the MXU lanes (weights stay OIHW in the pytree; XLA folds
        # the kernel relayout, which is tiny next to the activations)
        nhwc = getattr(self, "_data_layout", "nchw") == "nhwc"
        dn = ("NHWC", "OIHW", "NHWC") if nhwc else ("NCHW", "OIHW", "NCHW")
        y = lax.conv_general_dilated(
            x,
            weights[0],
            window_strides=p.stride,
            padding=[(p.padding[0], p.padding[0]), (p.padding[1], p.padding[1])],
            dimension_numbers=dn,
            feature_group_count=p.groups,
        )
        if p.use_bias:
            bias = weights[1]
            y = y + (bias[None, None, None, :] if nhwc
                     else bias[None, :, None, None])
        return [apply_activation(y, p.activation)]

    def flops(self):
        oshape = self.outputs[0].shape
        p: Conv2DParams = self.params
        cin = self.inputs[0].shape.logical_shape[1]
        return (
            2.0
            * oshape.num_elements()
            * (cin // p.groups)
            * p.kernel[0]
            * p.kernel[1]
        )


@dataclasses.dataclass(frozen=True)
class Pool2DParams:
    kernel: Tuple[int, int]
    stride: Tuple[int, int]
    padding: Tuple[int, int] = (0, 0)
    pool_type: str = "max"  # "max" | "avg"
    activation: ActiMode = ActiMode.NONE


class Pool2D(Op):
    op_type = OperatorType.POOL2D

    def infer_output_shapes(self, input_shapes):
        (ishape,) = input_shapes
        p: Pool2DParams = self.params
        n, c, h, w = [d for d in ishape.dims if not d.is_replica_dim]
        oh = (h.size + 2 * p.padding[0] - p.kernel[0]) // p.stride[0] + 1
        ow = (w.size + 2 * p.padding[1] - p.kernel[1]) // p.stride[1] + 1
        if oh <= 0 or ow <= 0:
            raise ShapeError(f"{self.name}: non-positive output spatial dims")
        dims = (
            ParallelDim(n.size, n.degree),
            ParallelDim(c.size, c.degree),
            ParallelDim(oh, h.degree),
            ParallelDim(ow, w.degree),
            ParallelDim(1, ishape.replica_degree, is_replica_dim=True),
        )
        return [ParallelTensorShape(dims, ishape.dtype)]

    def forward(self, inputs, weights, *, training=False, rng=None):
        (x,) = inputs
        p: Pool2DParams = self.params
        hw_pads = [(p.padding[0], p.padding[0]), (p.padding[1], p.padding[1])]
        if getattr(self, "_data_layout", "nchw") == "nhwc":
            pads = [(0, 0)] + hw_pads + [(0, 0)]
            dims = (1,) + p.kernel + (1,)
            strides = (1,) + p.stride + (1,)
        else:
            pads = [(0, 0), (0, 0)] + hw_pads
            dims = (1, 1) + p.kernel
            strides = (1, 1) + p.stride
        if p.pool_type == "max":
            init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
            y = lax.reduce_window(x, init, lax.max, dims, strides, pads)
        else:
            s = lax.reduce_window(x, 0.0, lax.add, dims, strides, pads)
            y = s / (p.kernel[0] * p.kernel[1])
        return [apply_activation(y, p.activation)]


@dataclasses.dataclass(frozen=True)
class EmbeddingParams:
    num_entries: int
    out_dim: int
    aggr: AggrMode = AggrMode.NONE
    dtype: DataType = DataType.FLOAT


class Embedding(Op):
    """Token embedding; attribute-parallel over the vocab dim.

    Reference: embedding.cc:132-196 — the weight's vocab dim carries the
    attribute-parallel degree; each shard looks up only ids in its range
    and the partial outputs sum (output replica degree = vocab degree).
    Here the masked lookup is one gather + where; XLA SPMD turns the
    partial sum into a psum over the vocab axis.
    """

    op_type = OperatorType.EMBEDDING

    def infer_output_shapes(self, input_shapes):
        (ishape,) = input_shapes
        p: EmbeddingParams = self.params
        data_dims = [d for d in ishape.dims if not d.is_replica_dim]
        out_replica = ishape.replica_degree * self.shard.attribute
        if p.aggr == AggrMode.NONE:
            kept = data_dims
        else:
            kept = data_dims[:-1]  # aggregate over the last (bag) dim
        dims = tuple(ParallelDim(d.size, d.degree) for d in kept) + (
            ParallelDim(p.out_dim, self.shard.channel),
            ParallelDim(1, out_replica, is_replica_dim=True),
        )
        return [ParallelTensorShape(dims, p.dtype)]

    def make_weight_specs(self, input_shapes):
        (ishape,) = input_shapes
        p: EmbeddingParams = self.params
        batch_degree = 1
        for d in ishape.dims:
            if not d.is_replica_dim:
                batch_degree *= d.degree
        weight = ParallelTensorShape(
            (
                ParallelDim(p.num_entries, self.shard.attribute),
                ParallelDim(p.out_dim, self.shard.channel),
                ParallelDim(1, batch_degree, is_replica_dim=True),
            ),
            p.dtype,
        )
        return [WeightSpec("weight", weight, DEFAULT_WEIGHT_INIT)]

    def forward(self, inputs, weights, *, training=False, rng=None):
        (ids,) = inputs
        p: EmbeddingParams = self.params
        table = weights[0]
        emb = jnp.take(table, ids, axis=0)
        if p.aggr == AggrMode.SUM:
            emb = jnp.sum(emb, axis=-2)
        elif p.aggr == AggrMode.AVG:
            emb = jnp.mean(emb, axis=-2)
        return [emb]


@dataclasses.dataclass(frozen=True)
class BatchMatmulParams:
    a_seq_length_dim: int = -1
    b_seq_length_dim: int = -1


class BatchMatmul(Op):
    """[b..., m, k] @ [b..., k, n] -> [b..., m, n].

    Reference: batch_matmul.cc (cublas strided-batched GEMM); the
    seq-length-dim fields mirror its FFIterationConfig truncation support
    (batch_matmul.cc:70-77).
    """

    op_type = OperatorType.BATCH_MATMUL

    def infer_output_shapes(self, input_shapes):
        a, b = input_shapes
        ad = [d for d in a.dims if not d.is_replica_dim]
        bd = [d for d in b.dims if not d.is_replica_dim]
        if len(ad) != len(bd):
            raise ShapeError(f"{self.name}: rank mismatch {len(ad)} vs {len(bd)}")
        if ad[-1].size != bd[-2].size:
            raise ShapeError(f"{self.name}: contraction mismatch")
        for da, db in zip(ad[:-2], bd[:-2]):
            if da.size != db.size or da.degree != db.degree:
                raise ShapeError(f"{self.name}: batch dims mismatch")
        if ad[-1].degree != bd[-2].degree:
            raise ShapeError(f"{self.name}: contraction degrees differ")
        out_replica = max(a.replica_degree, b.replica_degree) * ad[-1].degree
        dims = tuple(ParallelDim(d.size, d.degree) for d in ad[:-2]) + (
            ParallelDim(ad[-2].size, ad[-2].degree),
            ParallelDim(bd[-1].size, bd[-1].degree),
            ParallelDim(1, out_replica, is_replica_dim=True),
        )
        return [ParallelTensorShape(dims, a.dtype)]

    def forward(self, inputs, weights, *, training=False, rng=None):
        a, b = inputs
        # FFIterationConfig.seq_length early truncation
        # (batch_matmul.cc:70-77): positions past seq_length on the
        # declared seq dims are masked out — static shapes for XLA, the
        # masked work is dead and fuses away.
        seq_len = getattr(self, "_iter_seq_length", -1)
        p: BatchMatmulParams = self.params
        if seq_len > 0:
            a = self._mask_seq(a, p.a_seq_length_dim, seq_len)
            b = self._mask_seq(b, p.b_seq_length_dim, seq_len)
        return [jnp.matmul(a, b)]

    @staticmethod
    def _mask_seq(x, dim: int, seq_len: int):
        if dim < 0 or dim >= x.ndim:
            return x
        idx = jnp.arange(x.shape[dim])
        shape = [1] * x.ndim
        shape[dim] = x.shape[dim]
        return x * (idx < seq_len).reshape(shape).astype(x.dtype)

    def flops(self):
        a = self.inputs[0].shape.logical_shape
        n = self.outputs[0].shape.logical_shape[-1]
        import numpy as np

        return 2.0 * float(np.prod(a)) * n


def gated_mlp(x, w_gate, w_up, w_down):
    """W_down (silu(x W_gate) * (x W_up)): the SiLU-gated MLP."""
    return jnp.matmul(jax.nn.silu(jnp.matmul(x, w_gate))
                      * jnp.matmul(x, w_up), w_down)


@dataclasses.dataclass(frozen=True)
class GatedMLPParams:
    intermediate_size: int
    dtype: DataType = DataType.FLOAT


class GatedMLP(Op):
    """SiLU-gated MLP over the last axis, no biases: three weights
    `w_gate`, `w_up` [e, f] and `w_down` [f, e].  One op rather than
    three Linears and two elementwise ops because the gate and the up
    product are never wanted apart."""

    op_type = OperatorType.GATED_MLP

    def infer_output_shapes(self, input_shapes):
        (ishape,) = input_shapes
        last = [d for d in ishape.dims if not d.is_replica_dim][-1]
        if last.degree != 1 or not self.shard.is_trivial():
            raise ShapeError(f"{self.name}: the gated MLP is not sharded "
                             "inside (shard its batch)")
        return [ParallelTensorShape(ishape.dims, self.params.dtype)]

    def make_weight_specs(self, input_shapes):
        (ishape,) = input_shapes
        p: GatedMLPParams = self.params
        e, f = ishape.logical_shape[-1], p.intermediate_size
        rep = ParallelDim(1, ishape.total_degree, is_replica_dim=True)

        def w(rows, cols):
            return ParallelTensorShape(
                (ParallelDim(rows), ParallelDim(cols), rep), p.dtype)

        return [WeightSpec("w_gate", w(e, f), DEFAULT_WEIGHT_INIT),
                WeightSpec("w_up", w(e, f), DEFAULT_WEIGHT_INIT),
                WeightSpec("w_down", w(f, e), DEFAULT_WEIGHT_INIT)]

    def forward(self, inputs, weights, *, training=False, rng=None):
        return [gated_mlp(inputs[0], *weights)]

    def flops(self):
        return (6.0 * self.inputs[0].shape.num_elements()
                * self.params.intermediate_size)
