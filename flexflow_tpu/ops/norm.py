"""Normalization ops: LayerNorm, BatchNorm, Softmax.

Reference: src/ops/layer_norm.cc (custom CUDA welford kernels),
src/ops/batch_norm.cc (cuDNN BN with running stats),
src/ops/softmax.cc (cuDNN softmax).  TPU-first: expressed in jnp so XLA
fuses the reductions; BatchNorm's running stats are carried as explicit
(non-trainable) state entries updated functionally, and the batch-mean/var
psum across data-parallel shards falls out of SPMD (the array is globally
logical).
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import jax
import jax.numpy as jnp

from ..fftype import DataType, OperatorType
from ..initializer import ConstantInitializer, ZeroInitializer
from ..tensor import ParallelDim, ParallelTensorShape
from .op import Op, ShapeError, WeightSpec


@dataclasses.dataclass(frozen=True)
class LayerNormParams:
    axes: Tuple[int, ...]  # logical axes normalized over (e.g. (-1,))
    elementwise_affine: bool = True
    eps: float = 1e-5


class LayerNorm(Op):
    op_type = OperatorType.LAYER_NORM

    def infer_output_shapes(self, input_shapes):
        (ishape,) = input_shapes
        rank = ishape.logical_rank
        for ax in self.params.axes:
            d = [d for d in ishape.dims if not d.is_replica_dim][ax % rank]
            if d.degree != 1:
                raise ShapeError(f"{self.name}: normalized axis {ax} is partitioned")
        return [ishape]

    def make_weight_specs(self, input_shapes):
        p: LayerNormParams = self.params
        if not p.elementwise_affine:
            return []
        (ishape,) = input_shapes
        lshape = ishape.logical_shape
        rank = len(lshape)
        norm_shape = tuple(lshape[ax % rank] for ax in sorted(a % rank for a in p.axes))
        rep = ishape.total_degree
        dims = tuple(ParallelDim(s) for s in norm_shape) + (
            ParallelDim(1, rep, is_replica_dim=True),
        )
        wshape = ParallelTensorShape(dims, ishape.dtype)
        return [
            WeightSpec("gamma", wshape, ConstantInitializer(1.0)),
            WeightSpec("beta", wshape, ZeroInitializer()),
        ]

    def forward(self, inputs, weights, *, training=False, rng=None):
        (x,) = inputs
        p: LayerNormParams = self.params
        axes = tuple(a % x.ndim for a in p.axes)
        mean = jnp.mean(x, axis=axes, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=axes, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + p.eps)
        if p.elementwise_affine:
            gamma, beta = weights
            shape = [1] * x.ndim
            for i, ax in enumerate(sorted(axes)):
                shape[ax] = gamma.shape[i]
            y = y * gamma.reshape(shape) + beta.reshape(shape)
        return [y.astype(x.dtype)]


@dataclasses.dataclass(frozen=True)
class BatchNormParams:
    relu: bool = True  # reference batch_norm has fused relu option
    eps: float = 1e-5
    momentum: float = 0.9


class BatchNorm(Op):
    """NCHW batch norm.  Running stats live in weights[2:4] (non-trainable);
    forward returns updated stats via the op's `aux_state` convention
    handled by the executor."""

    op_type = OperatorType.BATCH_NORM
    has_aux_state = True  # weights[2:] are non-trainable state

    def infer_output_shapes(self, input_shapes):
        (ishape,) = input_shapes
        return [ishape]

    def make_weight_specs(self, input_shapes):
        (ishape,) = input_shapes
        c = ishape.logical_shape[1]
        cdeg = [d for d in ishape.dims if not d.is_replica_dim][1].degree
        rep = ishape.total_degree // cdeg
        dims = (ParallelDim(c, cdeg), ParallelDim(1, rep, is_replica_dim=True))
        ws = ParallelTensorShape(dims, ishape.dtype)
        return [
            WeightSpec("gamma", ws, ConstantInitializer(1.0)),
            WeightSpec("beta", ws, ZeroInitializer()),
            WeightSpec("running_mean", ws, ZeroInitializer()),
            WeightSpec("running_var", ws, ConstantInitializer(1.0)),
        ]

    def num_trainable_weights(self) -> int:
        return 2

    def forward(self, inputs, weights, *, training=False, rng=None):
        (x,) = inputs
        p: BatchNormParams = self.params
        gamma, beta, rmean, rvar = weights
        # channel position follows the physical layout (pcg/layout.py);
        # NHWC keeps the reduction over the vector lanes
        nhwc = getattr(self, "_data_layout", "nchw") == "nhwc"
        axes = (0, 1, 2) if nhwc else (0, 2, 3)
        bshape = (
            (1, 1, 1, -1) if nhwc else (1, -1, 1, 1)
        )
        if training:
            # one-pass stats (E[x^2] - E[x]^2): a single fused read of
            # the activation instead of two; f32 accumulation so the
            # subtraction stays stable under bf16 compute
            mean = jnp.mean(x, axis=axes, dtype=jnp.float32)
            # clamp: the subtraction can round negative for a
            # near-constant channel with a large offset, and rsqrt of a
            # negative poisons the step with NaN
            var = jnp.maximum(
                jnp.mean(jnp.square(x.astype(jnp.float32)), axis=axes)
                - jnp.square(mean),
                0.0,
            )
            new_rmean = p.momentum * rmean + (1 - p.momentum) * mean.astype(
                rmean.dtype
            )
            new_rvar = p.momentum * rvar + (1 - p.momentum) * var.astype(
                rvar.dtype
            )
        else:
            mean, var = rmean, rvar
            new_rmean, new_rvar = rmean, rvar
        scale = gamma.astype(var.dtype) * jax.lax.rsqrt(var + p.eps)
        shift = beta.astype(var.dtype) - mean * scale
        y = x * scale.reshape(bshape).astype(x.dtype) + shift.reshape(
            bshape
        ).astype(x.dtype)
        if p.relu:
            y = jax.nn.relu(y)
        return [y.astype(x.dtype), new_rmean, new_rvar]


@dataclasses.dataclass(frozen=True)
class SoftmaxParams:
    axis: int = -1


class Softmax(Op):
    op_type = OperatorType.SOFTMAX

    def infer_output_shapes(self, input_shapes):
        (ishape,) = input_shapes
        rank = ishape.logical_rank
        ax = self.params.axis % rank
        d = [d for d in ishape.dims if not d.is_replica_dim][ax]
        if d.degree != 1:
            raise ShapeError(f"{self.name}: softmax axis {ax} is partitioned")
        return [ishape]

    def forward(self, inputs, weights, *, training=False, rng=None):
        return [jax.nn.softmax(inputs[0], axis=self.params.axis)]


def rms_normalize(x, gamma, eps: float, zero_centered: bool = False):
    """x / sqrt(mean(x^2) + eps) * gamma over the last axis, reduced in
    float32 whatever x's dtype (a bf16 mean of 7,168 squares loses the
    low bits the scale needs), returned in x's dtype.  `zero_centered`:
    the stored gain is the gain's distance from 1 (`* (1 + gamma)`)."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                           + eps)
    g = gamma.astype(jnp.float32)
    return (y * (1.0 + g if zero_centered else g)).astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class RMSNormParams:
    eps: float = 1e-5
    # the gain is stored around zero and applied as 1 + gamma (the
    # qwen3_next family); False: stored around one and applied as it is
    zero_centered: bool = False


class RMSNorm(Op):
    """Root-mean-square norm over the last axis, one gain per channel
    (applied as `gamma`, or as `1 + gamma` where the params say the
    gain is stored zero-centred), no bias and no mean subtraction."""

    op_type = OperatorType.RMS_NORM

    def infer_output_shapes(self, input_shapes):
        (ishape,) = input_shapes
        last = [d for d in ishape.dims if not d.is_replica_dim][-1]
        if last.degree != 1:
            raise ShapeError(f"{self.name}: normalized axis is partitioned")
        return [ishape]

    def make_weight_specs(self, input_shapes):
        (ishape,) = input_shapes
        dims = (ParallelDim(ishape.logical_shape[-1]),
                ParallelDim(1, ishape.total_degree, is_replica_dim=True))
        identity = 0.0 if self.params.zero_centered else 1.0
        return [WeightSpec("gamma", ParallelTensorShape(dims, ishape.dtype),
                           ConstantInitializer(identity))]

    def forward(self, inputs, weights, *, training=False, rng=None):
        p: RMSNormParams = self.params
        return [rms_normalize(inputs[0], weights[0], p.eps, p.zero_centered)]

    def flops(self):
        return 4.0 * self.inputs[0].shape.num_elements()
