"""FFModel — the model-construction and training API.

TPU-native re-design of the reference's FFModel
(/root/reference/include/flexflow/model.h:326-956,
src/runtime/model.cc): the same ~50 layer-construction methods
(`dense`, `conv2d`, `multihead_attention`, `moe`, `embedding`, …),
`compile` (which here runs the strategy search and builds the jitted
SPMD step instead of launching GRAPH_OPTIMIZE on GPU0), and the
`fit`/`forward`/`backward`/`update`/`zero_gradients` training surface.

Execution differences from the reference, by design (SURVEY §7):
  * compile produces ONE jitted train-step over a `jax.sharding.Mesh`
    (Legion task graph + tracing + mapper + NCCL all collapse into it);
  * backward is `jax.grad` (no per-op backward launches);
  * `update` is a functional sharded optimizer step (gradient psum is
    emitted by SPMD, replacing optimizer_kernel.cu's ncclAllReduce).
"""
from __future__ import annotations

import contextlib
import dataclasses
import logging
import math
import time
import weakref
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .config import ConfigError, FFConfig, FFIterationConfig
from .executor import GraphExecutor
from .fftype import (
    ActiMode,
    AggrMode,
    CompMode,
    DataType,
    LossType,
    MetricsType,
    OpBinary,
    OperatorType,
    OpUnary,
)
from .initializer import Initializer
from .loss import Loss
from .metrics import Metrics, PerfMetrics
from .obs.trace import span
from .ops.attention import MultiHeadAttention, MultiHeadAttentionParams
from .ops.dense import (
    BatchMatmul,
    BatchMatmulParams,
    Conv2D,
    Conv2DParams,
    Embedding,
    EmbeddingParams,
    Linear,
    LinearParams,
    Pool2D,
    Pool2DParams,
)
from .ops.element import (
    Cast,
    CastParams,
    Dropout,
    DropoutParams,
    ElementBinary,
    ElementBinaryParams,
    ElementUnary,
    ElementUnaryParams,
)
from .ops.moe import (
    Aggregate,
    AggregateParams,
    AggregateSpec,
    Cache,
    CacheParams,
    GroupBy,
    GroupByParams,
    TopK,
    TopKParams,
)
from .ops.norm import (
    BatchNorm,
    BatchNormParams,
    LayerNorm,
    LayerNormParams,
    Softmax,
    SoftmaxParams,
)
from .ops.op import Op, ShapeError, ShardConfig
from .ops.shape import (
    Concat,
    ConcatParams,
    Flat,
    Gather,
    GatherParams,
    Mean,
    Pad,
    PadParams,
    Reduce,
    ReduceParams,
    Reshape,
    ReshapeParams,
    Reverse,
    ReverseParams,
    Split,
    SplitParams,
    Transpose,
    TransposeParams,
)
from .ops.sources import InputOp, SourceParams
from .optimizer import AdamOptimizer, Optimizer, SGDOptimizer
from .parallel.machine import make_mesh
from .pcg.graph import Graph, LoopRegion
from .strategy import (
    Strategy,
    apply_strategy,
    assign_views,
    data_parallel_strategy,
)
from .tensor import ParallelTensor, ParallelTensorShape

_log = logging.getLogger("flexflow_tpu.model")


class _OpenRegion:
    """What `FFModel.repeat` yields: the ops added while it is open are
    the region's; `carry(t)` names the pass's output; `passes()` (after
    the block) is every pass's output, stacked."""

    def __init__(self, ff: "FFModel", name: str, times: int,
                 carry_in: ParallelTensor):
        self._ff, self.name, self.times = ff, name, times
        self.carry_in = carry_in
        self.carry_out: Optional[ParallelTensor] = None
        self._first = len(ff.layers.ops)
        self._passes: Optional[ParallelTensor] = None

    def carry(self, t: ParallelTensor) -> None:
        """`t` is what a pass hands the next one in `carry_in`'s place
        (and, after the last pass, what the ops behind the region see)."""
        self.carry_out = t

    def passes(self) -> ParallelTensor:
        """`[times, ...]`: the carried output of every pass."""
        from .ops.loop import LoopPasses, LoopPassesParams

        ff = self._ff
        if ff._open_region is self:
            raise ConfigError(
                f"region {self.name!r}: passes() is asked after the "
                "`with` block (it is no op of the region)")
        if self._passes is None:
            op = LoopPasses(LoopPassesParams(self.times, self.name),
                            [self.carry_out], name=f"{self.name}_passes")
            self._passes = ff._add(op)
            ff.layers.regions = [
                dataclasses.replace(r, passes_op=op.name)
                if r.name == self.name else r for r in ff.layers.regions]
        return self._passes

    def close(self) -> LoopRegion:
        ops = self._ff.layers.ops[self._first:]
        name, cin, cout = self.name, self.carry_in, self.carry_out
        if not ops or cout is None:
            raise ConfigError(
                f"region {name!r} needs ops and carry(<its output>)")
        mine = {id(op) for op in ops}
        if id(cout.owner_op) not in mine:
            raise ConfigError(
                f"region {name!r}: carry({cout.name}) is not an output of "
                "the region's ops")
        if (tuple(cout.shape.logical_shape) != tuple(cin.shape.logical_shape)
                or cout.shape.dtype != cin.shape.dtype):
            raise ConfigError(
                f"region {name!r}: a pass must hand on what it was given: "
                f"{cout.name} {cout.shape} is not shaped like "
                f"{cin.name} {cin.shape}")
        for op in ops:
            if op.op_type == OperatorType.INPUT:
                raise ConfigError(
                    f"region {name!r}: an input ({op.name}) cannot be "
                    "made inside a region")
            outside = [t.name for t in op.inputs
                       if id(t.owner_op) not in mine and t is not cin]
            if outside:
                raise ConfigError(
                    f"region {name!r}: {op.name} reads {outside} from "
                    f"outside; a region takes ONE tensor in "
                    f"({cin.name}), which every pass replaces")
            if op.slot_state_entries():
                raise ConfigError(
                    f"region {name!r}: per-slot state inside a region "
                    f"({op.name}: {op.slot_state_entries()}) is not "
                    "built; only paged caches get a plane a pass")
            if op.cache_entries() and op.cache_planes != self.times:
                raise ConfigError(
                    f"region {name!r}: {op.name} ({type(op).__name__}) "
                    f"caches {op.cache_planes} plane(s), the region runs "
                    f"{self.times} passes; only MultiHeadAttention's "
                    "paged pool holds a plane a pass")
        return LoopRegion(name, self.times, tuple(op.name for op in ops),
                          cin.name, cout.name)


def device_put_like(saved, current):
    """device_put each saved leaf onto the matching current leaf's
    sharding — the carry idiom shared by recompile and the resilience
    supervisor's rollback."""
    return jax.tree.map(
        lambda v, cur: (
            jax.device_put(v, cur.sharding)
            if getattr(cur, "sharding", None) is not None
            else v
        ),
        saved, current,
    )


class FFModel:
    def __init__(self, config: Optional[FFConfig] = None):
        self.config = config or FFConfig()
        # run telemetry (obs/): the in-memory registry always; files,
        # log capture and request traces when FFConfig.trace_dir /
        # telemetry asks.  Spans (obs.trace.span) need neither.
        from .obs import RunTelemetry

        self.telemetry = RunTelemetry.from_config(self.config)
        self.layers = Graph()  # frontend (degree-1) graph
        self.operators: Optional[Graph] = None  # compiled strategy graph
        self.strategy: Optional[Strategy] = None
        self.mesh = None
        self.executor: Optional[GraphExecutor] = None
        self.optimizer: Optional[Optimizer] = None
        self.loss: Optional[Loss] = None
        self.metrics: Optional[Metrics] = None
        self.iter_config = FFIterationConfig()
        self._weights = None
        # compile(defer_weights=True): no weight is drawn and no
        # optimizer state made; set_weights() brings the weights, in
        # whatever precision they are to be held in
        self.weights_deferred = False
        # what a models/ builder records so that a decode twin is that
        # builder again (decoding.DecoderRecipe)
        self.decoder_recipe = None
        # or, where the builder knows no twin of its graph can be built,
        # why (decoding.decoder_recipe raises it as a ConfigError)
        self.not_served = None
        self._opt_state = None
        self._state = None
        self._step_fn = None
        self._step_cache: Dict[int, tuple] = {}
        # step functions that have run once (their first call compiles)
        self._stepped_fns = weakref.WeakSet()
        self._train_steps = 0  # train_step calls, the span's step index
        self._pending_moe = []  # (step, counts) a step returned, unread
        self._eval_fn = None
        self._rng = None
        self._label_replication = 1
        self._name_counts: Dict[str, int] = {}
        self._used_names: set = set()
        self._fwd_fn = None
        self._stop_training = False  # set by EarlyStopping-style callbacks
        self._cache_ops: List[Op] = []
        self._compiled_cache: Dict[str, Op] = {}
        self._pending_taps = None  # one-step-late cache taps
        self._open_region: Optional[_OpenRegion] = None  # `repeat`

    # ------------------------------------------------------------------
    # tensor / naming helpers
    # ------------------------------------------------------------------
    def _name(self, base: str, name: Optional[str]) -> str:
        if name:
            if name in self._used_names:
                raise ValueError(f"duplicate layer name: {name!r}")
            self._used_names.add(name)
            return name
        while True:
            n = self._name_counts.get(base, 0)
            self._name_counts[base] = n + 1
            candidate = f"{base}_{n}"
            if candidate not in self._used_names:
                self._used_names.add(candidate)
                return candidate

    def create_tensor(
        self,
        dims: Sequence[int],
        dtype: Union[DataType, str] = DataType.FLOAT,
        name: Optional[str] = None,
        create_grad: bool = True,
    ) -> ParallelTensor:
        shape = ParallelTensorShape.make(dims, DataType.from_any(
            dtype.value if isinstance(dtype, DataType) else dtype))
        op = InputOp(SourceParams(shape), [], name=self._name("input", name))
        self.layers.add_op(op)
        op.outputs[0].create_gradients = create_grad
        return op.outputs[0]

    def _add(self, op: Op):
        self.layers.add_op(op)
        if len(op.outputs) == 1:
            return op.outputs[0]
        return tuple(op.outputs)

    def set_output(self, t: ParallelTensor) -> None:
        """Say which op's output is the graph's (what the loss, the
        metrics and a decode step's logits read) where the graph has
        another sink beside it; without this it is the last sink."""
        self.layers.output_name = t.owner_op.name

    @contextlib.contextmanager
    def repeat(self, carry: ParallelTensor, times: int,
               name: Optional[str] = None):
        """A region of the graph that runs `times` times over ONE copy
        of its weights (pcg/graph.py `LoopRegion`):

            with ff.repeat(t, 4, name="loop") as loop:
                for i in range(layers):
                    t = ...                  # ops that read `t`
                loop.carry(t)                # what the next pass starts from
            per_pass = loop.passes()         # [4, ...], optional
            logits = ff.dense(t, ...)        # the last pass's output

        The ops added inside the block are the region.  They read ONE
        tensor from outside, `carry`, which every pass after the first
        replaces by the previous pass's `loop.carry(...)` tensor (same
        shape and dtype).  The executor runs the region as one
        `lax.scan` over the pass index: its weights exist once (in
        `weights`, `set_weights`, a checkpoint), a gradient through it
        is the sum over the passes, and an attention op inside it, in a
        paged twin, caches one plane of keys and values a pass.  What a
        region cannot be combined with yet is a `ConfigError` at
        compile, by name."""
        if self._open_region is not None:
            raise ConfigError(
                f"region {self._open_region.name!r} is open: regions do "
                "not nest")
        if int(times) < 1:
            raise ConfigError(f"a region runs at least once, got {times}")
        region = _OpenRegion(self, self._name("loop", name), int(times),
                             carry)
        self._open_region = region
        try:
            yield region
        finally:
            self._open_region = None
        self.layers.regions.append(region.close())

    # ------------------------------------------------------------------
    # layer API (reference model.h:326-712)
    # ------------------------------------------------------------------
    def dense(
        self,
        input: ParallelTensor,
        out_dim: int,
        activation: ActiMode = ActiMode.NONE,
        use_bias: bool = True,
        dtype: Union[DataType, str] = DataType.FLOAT,
        kernel_initializer: Optional[Initializer] = None,
        bias_initializer: Optional[Initializer] = None,
        name: Optional[str] = None,
    ) -> ParallelTensor:
        p = LinearParams(out_dim, use_bias, activation, DataType.from_any(
            dtype.value if isinstance(dtype, DataType) else dtype))
        op = Linear(p, [input], name=self._name("dense", name))
        if kernel_initializer is not None:
            op.weight_specs[0] = op.weight_specs[0].__class__(
                "kernel", op.weight_specs[0].shape, kernel_initializer
            )
        if use_bias and bias_initializer is not None:
            op.weight_specs[1] = op.weight_specs[1].__class__(
                "bias", op.weight_specs[1].shape, bias_initializer
            )
        return self._add(op)

    def conv2d(
        self,
        input: ParallelTensor,
        out_channels: int,
        kernel_h: int,
        kernel_w: int,
        stride_h: int = 1,
        stride_w: int = 1,
        padding_h: int = 0,
        padding_w: int = 0,
        activation: ActiMode = ActiMode.NONE,
        groups: int = 1,
        use_bias: bool = True,
        name: Optional[str] = None,
    ) -> ParallelTensor:
        p = Conv2DParams(
            out_channels,
            (kernel_h, kernel_w),
            (stride_h, stride_w),
            (padding_h, padding_w),
            groups,
            use_bias,
            activation,
        )
        return self._add(Conv2D(p, [input], name=self._name("conv2d", name)))

    def pool2d(
        self,
        input: ParallelTensor,
        kernel_h: int,
        kernel_w: int,
        stride_h: int,
        stride_w: int,
        padding_h: int = 0,
        padding_w: int = 0,
        pool_type: str = "max",
        activation: ActiMode = ActiMode.NONE,
        name: Optional[str] = None,
    ) -> ParallelTensor:
        p = Pool2DParams(
            (kernel_h, kernel_w),
            (stride_h, stride_w),
            (padding_h, padding_w),
            pool_type,
            activation,
        )
        return self._add(Pool2D(p, [input], name=self._name("pool2d", name)))

    def embedding(
        self,
        input: ParallelTensor,
        num_entries: int,
        out_dim: int,
        aggr: AggrMode = AggrMode.NONE,
        dtype: Union[DataType, str] = DataType.FLOAT,
        kernel_initializer: Optional[Initializer] = None,
        name: Optional[str] = None,
    ) -> ParallelTensor:
        p = EmbeddingParams(num_entries, out_dim, aggr, DataType.from_any(
            dtype.value if isinstance(dtype, DataType) else dtype))
        op = Embedding(p, [input], name=self._name("embedding", name))
        if kernel_initializer is not None:
            op.weight_specs[0] = op.weight_specs[0].__class__(
                "weight", op.weight_specs[0].shape, kernel_initializer
            )
        return self._add(op)

    def multihead_attention(
        self,
        query: ParallelTensor,
        key: ParallelTensor,
        value: ParallelTensor,
        embed_dim: int,
        num_heads: int,
        kdim: int = 0,
        vdim: int = 0,
        dropout: float = 0.0,
        bias: bool = False,
        add_bias_kv: bool = False,
        add_zero_attn: bool = False,
        causal: bool = False,
        name: Optional[str] = None,
        decode_max_seq: int = 0,
        kv_page_size: int = 0,
        kv_num_blocks: int = 0,
        kv_kernel: str = "gather",
        window_ring: int = 0,
        **grouped_rotary_gated,
    ) -> ParallelTensor:
        """`grouped_rotary_gated`: the further fields of
        `MultiHeadAttentionParams` by name (`num_kv_heads`, `qk_norm`,
        `rotary_dim`, `output_gate`, `sliding_window`, ...), all off by
        default.  `window_ring`: rows a slot of a window layer's decode
        twin (ops/attention.py)."""
        p = MultiHeadAttentionParams(
            embed_dim, num_heads, kdim, vdim, dropout, bias, add_bias_kv,
            add_zero_attn, causal, **grouped_rotary_gated,
        )
        return self._add(
            MultiHeadAttention(p, [query, key, value],
                               name=self._name("attention", name),
                               decode_max_seq=decode_max_seq,
                               kv_page_size=kv_page_size,
                               kv_num_blocks=kv_num_blocks,
                               kv_kernel=kv_kernel,
                               window_ring=window_ring,
                               # inside `repeat`: a plane a pass
                               kv_planes=(self._open_region.times
                                          if self._open_region else 1))
        )

    def mla_attention(self, input, positions, params, name=None,
                      decode_max_seq: int = 0, kv_page_size: int = 0,
                      kv_num_blocks: int = 0, kv_kernel: str = "gather",
                      picks=None):
        """Multi-head latent attention (ops/mla.py): `params` is an
        `MLAParams`; the cache keywords are `multihead_attention`'s,
        and build the paged LATENT cache.  `positions` is None for an
        op that rotates nothing (`params.nope`).  With an indexer
        (`params.indexer`) a "full" op returns (output, picks) and a
        "shared" one takes such `picks`."""
        from .ops.mla import MLAttention

        return self._add(MLAttention(
            params, [t for t in (input, positions, picks) if t is not None],
            name=self._name("mla_attention", name),
            decode_max_seq=decode_max_seq, kv_page_size=kv_page_size,
            kv_num_blocks=kv_num_blocks, kv_kernel=kv_kernel))

    def gated_delta_net(self, input, params, name=None,
                        slot_state: bool = False):
        """A Gated-DeltaNet mixer (ops/gated_delta_net.py): `params` is
        a `GatedDeltaNetParams`; `slot_state` builds the serving twin's
        op, which carries a conv tail and a delta-rule matrix a slot."""
        from .ops.gated_delta_net import GatedDeltaNet

        return self._add(GatedDeltaNet(
            params, [input], name=self._name("gated_delta_net", name),
            slot_state=slot_state))

    def eva_attention(self, input, params, name=None,
                      slot_state: bool = False, max_seq: int = 0):
        """EVA chunked attention (ops/eva_attention.py): `params` is an
        `EvaAttentionParams`; `slot_state` builds the serving twin's
        op, which carries a window of keys and values and a store of
        chunk summaries a slot, for sequences of up to `max_seq`."""
        from .ops.eva_attention import EvaAttention

        return self._add(EvaAttention(
            params, [input], name=self._name("eva_attention", name),
            slot_state=slot_state, max_seq=max_seq))

    def kimi_delta_attention(self, input, params, name=None):
        """A Kimi Delta Attention mixer (ops/kimi_delta_attention.py):
        the delta rule with a decay per channel; `params` is a
        `KimiDeltaAttentionParams`.  Stateless (training) only."""
        from .ops.kimi_delta_attention import KimiDeltaAttention

        return self._add(KimiDeltaAttention(
            params, [input], name=self._name("kimi_delta_attention", name)))

    def short_conv(self, input, kernel: int = 3, name=None):
        """A gated short convolution over the sequence
        (ops/short_conv.py), `kernel` taps a channel."""
        from .ops.short_conv import ShortConv, ShortConvParams

        return self._add(ShortConv(
            ShortConvParams(input.shape.logical_shape[-1], kernel), [input],
            name=self._name("short_conv", name)))

    def gated_mlp(self, input, intermediate_size: int, name=None):
        from .ops.dense import GatedMLP, GatedMLPParams

        return self._add(GatedMLP(GatedMLPParams(intermediate_size), [input],
                                  name=self._name("gated_mlp", name)))

    def routed_experts(self, input, params, name=None):
        """One chip's share of a routed-expert layer plus its shared
        expert (ops/routed_experts.py): `params` is a
        `RoutedExpertsParams` naming the experts held here."""
        from .ops.routed_experts import RoutedExperts

        return self._add(RoutedExperts(
            params, [input], name=self._name("routed_experts", name)))

    def batch_matmul(
        self,
        a: ParallelTensor,
        b: ParallelTensor,
        a_seq_length_dim: int = -1,
        b_seq_length_dim: int = -1,
        name: Optional[str] = None,
    ) -> ParallelTensor:
        p = BatchMatmulParams(a_seq_length_dim, b_seq_length_dim)
        return self._add(BatchMatmul(p, [a, b], name=self._name("batch_matmul", name)))

    # -- elementwise binary ---------------------------------------------
    def _binary(self, kind: OpBinary, x, y, inplace_a=False, name=None):
        p = ElementBinaryParams(kind, inplace_a)
        return self._add(
            ElementBinary(p, [x, y], name=self._name(kind.value, name))
        )

    def add(self, x, y, inplace_a=False, name=None):
        return self._binary(OpBinary.ADD, x, y, inplace_a, name)

    def subtract(self, x, y, inplace_a=False, name=None):
        return self._binary(OpBinary.SUB, x, y, inplace_a, name)

    def multiply(self, x, y, inplace_a=False, name=None):
        return self._binary(OpBinary.MUL, x, y, inplace_a, name)

    def divide(self, x, y, inplace_a=False, name=None):
        return self._binary(OpBinary.DIV, x, y, inplace_a, name)

    def max(self, x, y, name=None):
        return self._binary(OpBinary.MAX, x, y, False, name)

    def min(self, x, y, name=None):
        return self._binary(OpBinary.MIN, x, y, False, name)

    # -- elementwise unary ----------------------------------------------
    def _unary(self, kind: OpUnary, x, scalar=0.0, inplace=False, name=None):
        p = ElementUnaryParams(kind, inplace, scalar)
        return self._add(ElementUnary(p, [x], name=self._name(kind.value, name)))

    def exp(self, x, name=None):
        return self._unary(OpUnary.EXP, x, name=name)

    def log(self, x, name=None):
        return self._unary(OpUnary.LOG, x, name=name)

    def sin(self, x, name=None):
        return self._unary(OpUnary.SIN, x, name=name)

    def cos(self, x, name=None):
        return self._unary(OpUnary.COS, x, name=name)

    def relu(self, x, inplace=True, name=None):
        return self._unary(OpUnary.RELU, x, inplace=inplace, name=name)

    def gelu(self, x, name=None):
        return self._unary(OpUnary.GELU, x, name=name)

    def sigmoid(self, x, name=None):
        return self._unary(OpUnary.SIGMOID, x, name=name)

    def tanh(self, x, name=None):
        return self._unary(OpUnary.TANH, x, name=name)

    def elu(self, x, inplace=True, name=None):
        return self._unary(OpUnary.ELU, x, inplace=inplace, name=name)

    def identity(self, x, name=None):
        return self._unary(OpUnary.IDENTITY, x, name=name)

    def rsqrt(self, x, name=None):
        return self._unary(OpUnary.RSQRT, x, name=name)

    def sqrt(self, x, name=None):
        return self._unary(OpUnary.SQRT, x, name=name)

    def erf(self, x, name=None):
        return self._unary(OpUnary.ERF, x, name=name)

    def floor(self, x, name=None):
        return self._unary(OpUnary.FLOOR, x, name=name)

    def pow(self, x, exponent: float, name=None):
        return self._unary(OpUnary.POW, x, scalar=exponent, name=name)

    def scalar_multiply(self, x, scalar: float, inplace=True, name=None):
        return self._unary(OpUnary.SCALAR_MULTIPLY, x, scalar=scalar, name=name)

    def scalar_add(self, x, scalar: float, inplace=True, name=None):
        return self._unary(OpUnary.SCALAR_ADD, x, scalar=scalar, name=name)

    def scalar_sub(self, x, scalar: float, inplace=True, name=None):
        return self._unary(OpUnary.SCALAR_SUB, x, scalar=scalar, name=name)

    def scalar_true_divide(self, x, scalar: float, inplace=True, name=None):
        return self._unary(OpUnary.SCALAR_TRUE_DIV, x, scalar=scalar, name=name)

    # -- norm / softmax --------------------------------------------------
    def softmax(self, input, axis: int = -1, name=None):
        return self._add(
            Softmax(SoftmaxParams(axis), [input], name=self._name("softmax", name))
        )

    def layer_norm(
        self,
        input,
        axes: Sequence[int],
        elementwise_affine: bool = True,
        eps: float = 1e-5,
        name=None,
    ):
        p = LayerNormParams(tuple(axes), elementwise_affine, eps)
        return self._add(LayerNorm(p, [input], name=self._name("layer_norm", name)))

    def rms_norm(self, input, eps: float = 1e-5, name=None,
                 zero_centered: bool = False):
        from .ops.norm import RMSNorm, RMSNormParams

        return self._add(RMSNorm(RMSNormParams(eps, zero_centered), [input],
                                 name=self._name("rms_norm", name)))

    def batch_norm(self, input, relu: bool = True, eps: float = 1e-5,
                   momentum: float = 0.9, name=None):
        p = BatchNormParams(relu, float(eps), float(momentum))
        return self._add(BatchNorm(p, [input], name=self._name("batch_norm", name)))

    # -- shape ops -------------------------------------------------------
    def concat(self, tensors: Sequence[ParallelTensor], axis: int, name=None):
        return self._add(
            Concat(ConcatParams(axis), list(tensors), name=self._name("concat", name))
        )

    def split(self, input, sizes: Union[int, Sequence[int]], axis: int, name=None):
        if isinstance(sizes, int):
            dim_size = input.shape.logical_shape[axis]
            sizes = [dim_size // sizes] * sizes
        p = SplitParams(tuple(sizes), axis)
        return self._add(Split(p, [input], name=self._name("split", name)))

    def flat(self, input, name=None):
        return self._add(Flat(None, [input], name=self._name("flat", name)))

    def weight_tensor(self, array, trainable: bool = True, name=None):
        """A standalone parameter as a tensor (reference OP_WEIGHT /
        torch AttributeNode): initialized from `array`, trainable by
        default."""
        from .initializer import ArrayInitializer
        from .ops.op import WeightSpec
        from .ops.sources import SourceParams, WeightOp

        arr = np.asarray(array)
        shape = ParallelTensorShape.make(
            arr.shape, DataType.from_any(str(arr.dtype))
        )
        op = WeightOp(SourceParams(shape, "weight", trainable), [],
                      name=self._name("weight", name))
        op.weight_specs = [
            WeightSpec("value", shape, ArrayInitializer(arr))
        ]
        out = self._add(op)
        out.create_gradients = trainable
        return out

    def expand(self, input, sizes: Sequence[int], name=None):
        """Broadcast size-1 dims (torch Tensor.expand)."""
        from .ops.shape import Expand, ExpandParams

        p = ExpandParams(tuple(int(s) for s in sizes))
        return self._add(Expand(p, [input], name=self._name("expand", name)))

    def reshape(self, input, shape: Sequence[int], name=None):
        p = ReshapeParams(tuple(shape))
        return self._add(Reshape(p, [input], name=self._name("reshape", name)))

    def transpose(self, input, perm: Sequence[int], name=None):
        p = TransposeParams(tuple(perm))
        return self._add(Transpose(p, [input], name=self._name("transpose", name)))

    def reverse(self, input, axis: int, name=None):
        return self._add(
            Reverse(ReverseParams(axis), [input], name=self._name("reverse", name))
        )

    def pad(self, input, pads: Sequence[Sequence[int]], value: float = 0.0,
            name=None):
        """Constant-pad: pads is ((before, after), ...) per logical dim."""
        p = PadParams(tuple((int(b), int(a)) for b, a in pads), float(value))
        return self._add(Pad(p, [input], name=self._name("pad", name)))

    def reduce_sum(self, input, axes: Sequence[int], keepdims: bool = False, name=None):
        p = ReduceParams(tuple(axes), keepdims, "sum")
        return self._add(Reduce(p, [input], name=self._name("reduce_sum", name)))

    def mean(self, input, axes: Sequence[int], keepdims: bool = False, name=None):
        p = ReduceParams(tuple(axes), keepdims, "mean")
        return self._add(Mean(p, [input], name=self._name("mean", name)))

    def cast(self, input, dtype: Union[DataType, str], name=None):
        p = CastParams(DataType.from_any(
            dtype.value if isinstance(dtype, DataType) else dtype))
        return self._add(Cast(p, [input], name=self._name("cast", name)))

    def dropout(self, input, rate: float, seed: int = 0, name=None):
        p = DropoutParams(rate, seed)
        return self._add(Dropout(p, [input], name=self._name("dropout", name)))

    def gather(self, input, index, axis: int = 0, name=None):
        p = GatherParams(axis)
        return self._add(Gather(p, [input, index], name=self._name("gather", name)))

    # -- MoE -------------------------------------------------------------
    def top_k(self, input, k: int, sorted: bool = False, name=None):
        return self._add(TopK(TopKParams(k, sorted), [input], name=self._name("topk", name)))

    def group_by(self, data, assign, n: int, alpha: float, name=None):
        return self._add(
            GroupBy(GroupByParams(n, alpha), [data, assign], name=self._name("group_by", name))
        )

    def aggregate(self, gate_scores, assign, gate_full, expert_out, n: int,
                  lambda_bal: float = 0.0, name=None):
        p = AggregateParams(n, lambda_bal)
        return self._add(
            Aggregate(p, [gate_scores, assign, gate_full, expert_out],
                      name=self._name("aggregate", name))
        )

    def aggregate_spec(self, gate_scores, assign, gate_full, expert_out, n: int,
                       lambda_bal: float = 0.0, name=None):
        p = AggregateParams(n, lambda_bal)
        op = AggregateSpec(p, [gate_scores, assign, gate_full, expert_out],
                           name=self._name("aggregate_spec", name))
        out = self._add(op)
        self._label_replication = op.inputs[1].shape.logical_shape[-1]
        return out

    def cache(self, input, num_batches: int, *, score_fn=None, name=None):
        """Identity passthrough accumulating a host-side staleness score
        (reference src/ops/cache.cc, score_f moe.cc:40-63).  score_fn, if
        given, is called with this FFModel after every fit batch; its
        float feeds op.trigger for recompile_on_condition."""
        if score_fn is not None and not callable(score_fn):
            raise TypeError(f"score_fn must be callable, got {type(score_fn)}")
        op = Cache(CacheParams(num_batches), [input],
                   name=self._name("cache", name))
        op.score_fn = score_fn
        self._add(op)
        return op.outputs[0]

    def moe(
        self,
        input: ParallelTensor,
        num_exp: int,
        num_select: int,
        expert_hidden_size: int,
        alpha: float = 2.0,
        lambda_bal: float = 0.0,
        name=None,
    ) -> ParallelTensor:
        """MoE composite (reference src/ops/moe.cc:20-44): gate -> topk ->
        group_by -> per-expert FFN -> aggregate.  The expert FFN here is a
        batched dense over the stacked expert dim, so expert parallelism
        is sharding that dim (ShardConfig.expert).

        Rank-3 inputs [b, s, h] are flattened to [b*s, h] tokens around
        the dispatch and restored afterwards (the reference's group_by
        is 2-D only; its encoder path moe.cc:100-130 is dead code in its
        own example main)."""
        orig_shape = input.shape.logical_shape
        if len(orig_shape) == 3:
            b, s, h = orig_shape
            input = self.reshape(input, [b * s, h])
        gate = self.dense(input, num_exp, ActiMode.NONE)
        gate_sm = self.softmax(gate)
        topk_out = self.top_k(gate_sm, num_select)
        values, assign = topk_out
        grouped = self.group_by(input, assign, num_exp, alpha)
        # per-expert FFN: [n, cap, d] -> [n, cap, hidden]
        hidden = self.experts_dense(grouped, expert_hidden_size, activation=ActiMode.RELU)
        out = self.aggregate(values, assign, gate_sm, hidden, num_exp, lambda_bal,
                             name=name)
        if len(orig_shape) == 3:
            out = self.reshape(out, [orig_shape[0], orig_shape[1],
                                     expert_hidden_size])
        return out

    def lstm(self, input, hidden_size: int, return_sequences: bool = True,
             name=None):
        """Fused lax.scan LSTM (reference legacy nmt/ LSTM rebuilt as a
        first-class op, ops/recurrent.py)."""
        from .ops.recurrent import LSTM, LSTMParams

        p = LSTMParams(hidden_size, return_sequences)
        return self._add(LSTM(p, [input], name=self._name("lstm", name)))

    def experts_dense(self, grouped, out_dim: int, activation=ActiMode.NONE,
                      use_bias: bool = True, name=None):
        """Batched per-expert dense over stacked [n, cap, d] expert inputs."""
        from .ops.experts import ExpertsDense, ExpertsDenseParams

        p = ExpertsDenseParams(out_dim, use_bias, activation)
        return self._add(
            ExpertsDense(p, [grouped], name=self._name("experts_dense", name))
        )

    # ------------------------------------------------------------------
    # compile (reference FFModel::compile model.cc:2487-3167)
    # ------------------------------------------------------------------
    def compile(
        self,
        optimizer: Optional[Optimizer] = None,
        loss_type: Union[LossType, str] = LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics: Sequence[Union[MetricsType, str]] = (MetricsType.ACCURACY,),
        comp_mode: CompMode = CompMode.TRAINING,
        strategy: Optional[Strategy] = None,
        devices: Optional[Sequence] = None,
        seed: Optional[int] = None,
        defer_weights: bool = False,
    ):
        """`defer_weights=True` compiles a model that is only ever
        SERVED: the graph passes and the executor, op state (cache
        pools), but no weight drawn at random, no optimizer state and
        no train step.  `set_weights()` then holds what it is given as
        it is given (a server's bf16 weights stay bf16: resident once,
        in the precision they are computed in)."""
        t0 = time.perf_counter()
        with span("compile") as sp:
            result = self._compile_inner(
                optimizer=optimizer, loss_type=loss_type, metrics=metrics,
                comp_mode=comp_mode, strategy=strategy, devices=devices,
                seed=seed, defer_weights=defer_weights,
            )
            sp.set(ops=len(self.operators.topo_order()))
        self.telemetry.metrics.gauge("compile/total_ms").set(
            (time.perf_counter() - t0) * 1e3
        )
        return result

    def _stamp_catalog(self, strategy: Strategy) -> None:
        """Pin the catalog identity a FRESHLY searched trace used, so
        replay on another host can't silently resolve different rules
        (rewrite.rules_for_replay checks the hash).  Only ever called
        on this process's own search results — stamping an imported or
        store-restored trace with the LOCAL catalog's hash would
        fabricate provenance and defeat the replay check."""
        if strategy.catalog is not None or not any(
            str(n).startswith("taso_rule_") for n, _ in strategy.rewrites
        ):
            return
        from .pcg.rewrite import catalog_fingerprint, catalog_for_config

        path = catalog_for_config(self.config)
        if path:
            strategy.catalog = catalog_fingerprint(path)

    def _compile_inner(
        self,
        optimizer: Optional[Optimizer] = None,
        loss_type: Union[LossType, str] = LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics: Sequence[Union[MetricsType, str]] = (MetricsType.ACCURACY,),
        comp_mode: CompMode = CompMode.TRAINING,
        strategy: Optional[Strategy] = None,
        devices: Optional[Sequence] = None,
        seed: Optional[int] = None,
        defer_weights: bool = False,
    ):
        cfg = self.config
        tel = self.telemetry
        self.weights_deferred = bool(defer_weights)
        self._compile_args = {
            "loss_type": loss_type,
            "metrics": tuple(metrics),
            "comp_mode": comp_mode,
            "devices": list(devices) if devices is not None else None,
        }
        self.optimizer = optimizer or SGDOptimizer(
            lr=cfg.learning_rate, weight_decay=cfg.weight_decay
        )
        # Reference convention (loss_functions.cu): a model ending in
        # Softmax feeds probabilities to the loss, not logits.
        sink_is_softmax = self.layers.sink_op().op_type == OperatorType.SOFTMAX
        self.loss = Loss(loss_type, from_logits=not sink_is_softmax)
        self.metrics = Metrics(self.loss.loss_type, metrics)
        self._fwd_fn = None

        num_devices = len(devices) if devices is not None else cfg.resolve_num_devices()

        # compiled-step persistence half of the artifact store: settle
        # where XLA's cache lives BEFORE anything jit-executes so a
        # restarted process re-loads executables instead of recompiling
        # (env var > config > on-by-default on accelerators; store/,
        # docs/STORE.md)
        from .store import enable_compilation_cache

        enable_compilation_cache(cfg)

        if strategy is None and cfg.import_strategy_file:
            strategy = Strategy.load(cfg.import_strategy_file)
        if strategy is None:
            if cfg.search_budget > 0 and not cfg.only_data_parallel:
                self._no_region_under(
                    "the strategy search (its substitutions and its "
                    "costs walk a flat graph): compile with "
                    "only_data_parallel or an explicit strategy")
                # reference: Unity graph_optimize is the default search
                # path (GRAPH_OPTIMIZE_TASK_ID, graph.cc:2046); MCMC is
                # the legacy SysML'19 path (model.cc:3285).  The
                # strategy store wraps either: a warm entry for (graph,
                # mesh, simulator version) skips the search entirely
                # (search_stats records store_hit)
                from .pcg.search import mcmc_search, unity_search
                from .store import cached_search

                def _run_search():
                    if cfg.search_algo == "mcmc":
                        s = mcmc_search(self, num_devices)
                    else:
                        s = unity_search(self, num_devices)
                    # stamp the catalog identity BEFORE the store
                    # publish so restored entries carry the provenance
                    # their replay check needs (see _stamp_catalog)
                    self._stamp_catalog(s)
                    return s

                t_search = time.perf_counter()
                with span("search", algo=cfg.search_algo,
                          devices=num_devices) as sp:
                    strategy = cached_search(self, num_devices, _run_search)
                    sp.set(evaluations=int((getattr(
                        strategy, "search_stats", None) or {}).get(
                            "evals", 0)))
                tel.metrics.gauge("compile/search_ms").set(
                    (time.perf_counter() - t_search) * 1e3
                )
            else:
                strategy = data_parallel_strategy(num_devices)
        self.strategy = strategy
        if cfg.export_strategy_file:
            strategy.save(cfg.export_strategy_file)
        with span("compile.passes"):
            self._build_executor(strategy, devices, num_devices, comp_mode)
        if defer_weights:
            with span("init_state"):
                self._weights, self._state = self.executor.init_weights(
                    seed if seed is not None else cfg.seed, state_only=True)
            self._rng = jax.random.key(cfg.seed)
            return self
        # init_weights jit-executes eagerly, so this span IS a real XLA
        # compile; build_step/eval/forward only stage traces (their XLA
        # compile lands in the first train step — docs/OBSERVABILITY.md)
        with span("init_weights"):
            self._weights, self._state = self.executor.init_weights(
                seed if seed is not None else cfg.seed
            )
        # ZeRO-1 layout: slots move to their 1/N per-device shard here,
        # so every downstream consumer (step fn, checkpoint save/restore,
        # recompile's device_put_like) inherits the sharded placement
        with span("compile.opt_state"):
            self._opt_state = self.executor.shard_opt_state(
                self.optimizer.init_state(self._weights)
            )
        with span("build_step_fns", **self._attention_core_counts()):
            self._step_fn = self.executor.build_step()
            self._eval_fn = self.executor.build_eval_step()
            self._fwd_fn = self.executor.build_forward()
        self._step_cache[self.iter_config.seq_length] = (
            self._step_fn, self._eval_fn, self._fwd_fn,
        )
        self._rng = jax.random.key(cfg.seed)
        if cfg.export_compgraph_file:
            self.layers.export_dot(cfg.export_compgraph_file)
        if cfg.export_taskgraph_file:
            cost_fn = None
            if cfg.include_costs_dot_graph:
                # reference --include-costs-dot-graph (config.h:145):
                # annotate each node with its simulated forward cost
                from .sim.machine_model import make_machine_model
                from .sim.simulator import OpCostModel

                cm = OpCostModel(make_machine_model(cfg, num_devices))
                cost_fn = lambda op: cm.cost(op).forward_time  # noqa: E731
            self.operators.export_dot(
                cfg.export_taskgraph_file,
                include_costs=cfg.include_costs_dot_graph,
                cost_fn=cost_fn,
            )
        return self

    def _no_region_under(self, feature: str) -> None:
        """ConfigError, by name, where a graph with a repeated region
        meets a feature that cannot take one yet: never a fallback."""
        if self.layers.regions:
            raise ConfigError(
                f"region {self.layers.regions[0].name!r} (FFModel.repeat) "
                f"cannot be compiled under {feature}")

    def _build_executor(self, strategy: Strategy, devices, num_devices: int,
                        comp_mode: CompMode) -> None:
        """The graph passes between the strategy and the weights: replay
        the rewrite trace, fuse, apply the strategy, assign views, make
        the mesh, and build the GraphExecutor over them."""
        cfg = self.config
        tel = self.telemetry
        # replay the strategy's graph-rewrite trace (reference: the
        # winning GraphXfer rewrites applied by graph_optimize,
        # substitution.cc:1898-1945), then apply + cancel redundant
        # parallel-op boundaries
        compiled_frontend = self.layers
        if strategy.rewrites:
            self._no_region_under("a strategy's graph rewrites")
        if cfg.perform_fusion:
            self._no_region_under("--fusion (perform_fusion)")
        if strategy.pipeline:
            self._no_region_under("pipeline blocks (strategy.pipeline)")
        if strategy.rewrites:
            from .pcg.rewrite import apply_rewrites, rules_for_replay

            compiled_frontend = apply_rewrites(
                compiled_frontend, strategy.rewrites, rules_for_replay(cfg, strategy)
            )
        if cfg.perform_fusion:
            # reference --fusion (apply_fusion model.cc:2495): fold
            # trailing activations into their producers, skipping
            # anything the strategy names
            from .pcg.rewrite import fuse_activations

            protected = set(strategy.edge_ops) | set(strategy.shard_configs)
            compiled_frontend = fuse_activations(compiled_frontend, protected)
        self._compiled_frontend = compiled_frontend
        from .pcg.rewrite import cancel_all_inverse_parallel_ops

        self.operators = cancel_all_inverse_parallel_ops(
            apply_strategy(compiled_frontend, strategy)
        )
        # (ops are rebuilt under their names, and a region names its ops)
        self.operators.regions = list(compiled_frontend.regions)
        self.operators.output_name = compiled_frontend.output_name
        # multi-slice execution (topology/, docs/TOPOLOGY.md): lower the
        # strategy's placement (which mesh axis spans the DCN boundary)
        # to a two-level execution mesh — a leading slice dim plus the
        # placement axis's intra-slice remainder — so the hierarchical
        # grad-reduction re-specs can name the intra axis and the
        # C-order device layout aligns axes with physical slices.
        # Search-facing surfaces (strategy.mesh_axes, store keys,
        # simulator costs) keep the UNEXPANDED axes; only view
        # assignment and the jax Mesh see the expansion.
        exec_axes = strategy.mesh_axes
        hier_axis = None
        if cfg.slices > 1 and not strategy.pipeline:
            from .topology.hierarchy import (
                SLICE_AXIS,
                expand_mesh_axes,
                legal_placements,
                resolve_placement,
            )

            if num_devices % cfg.slices:
                # a degraded mesh (elastic recompile on survivors) may
                # not split into equal slices: execute flat rather
                # than failing recovery
                _log.warning(
                    "%d devices do not split into %d slices; executing "
                    "flat", num_devices, cfg.slices,
                )
            elif SLICE_AXIS in strategy.mesh_axes:
                _log.warning(
                    "mesh axis %r collides with the reserved slice "
                    "axis; executing flat (placement-less)", SLICE_AXIS,
                )
            else:
                placement = strategy.placement
                if placement is not None and placement not in \
                        legal_placements(strategy.mesh_axes, cfg.slices):
                    # imported/exported strategies can carry a placement
                    # from a different slice config: degrade to the
                    # default like the simulator and MCMC do, never
                    # crash compile over it
                    _log.warning(
                        "strategy placement %r is not legal for mesh %s "
                        "with %d slices; using the default placement",
                        placement, dict(strategy.mesh_axes), cfg.slices,
                    )
                    placement = None
                if placement is None:
                    placement = resolve_placement(
                        strategy.mesh_axes, cfg.slices
                    )
                if placement is None:
                    _log.warning(
                        "no mesh axis of %s is divisible by %d slices; "
                        "executing flat (cross-slice collectives "
                        "unsynthesized)", dict(strategy.mesh_axes),
                        cfg.slices,
                    )
                else:
                    exec_axes, hier_axis = expand_mesh_axes(
                        strategy.mesh_axes, cfg.slices, placement
                    )
                    _log.info(
                        "multi-slice execution: placement=%s over %d "
                        "slices, exec mesh %s%s", placement, cfg.slices,
                        exec_axes,
                        (f" (hierarchical reduction over {hier_axis!r})"
                         if hier_axis else ""),
                    )
        self._exec_axes = exec_axes
        assign_views(self.operators, exec_axes)
        self.mesh = make_mesh(exec_axes, devices)

        pipeline_plan = None
        if strategy.pipeline:
            from .parallel.pipeline_plan import plan_pipeline

            pipeline_plan = plan_pipeline(
                self.operators, strategy.pipeline, strategy.mesh_axes
            )
        # effective ZeRO stage: search-chosen (riding the strategy, so
        # store-restored winners replay their stage) over the config
        # knob (docs/PERF.md "The ZeRO ladder")
        zero_stage = (
            strategy.zero_stage if strategy.zero_stage is not None
            else cfg.zero_stage
        )
        # searched per-segment remat plan (docs/PERF.md "Searched
        # rematerialization"): rides the strategy like the ZeRO stage,
        # so store-restored / imported winners replay their plan; the
        # global --remat bool remains the plan-less fallback
        remat_plan = getattr(strategy, "remat", None)
        if remat_plan is not None:
            _log.info(
                "searched remat plan: %d segment(s) checkpointed (%s)",
                len(remat_plan),
                ",".join(str(i) for i in remat_plan) or "none",
            )
        self.executor = GraphExecutor(
            self.operators,
            self.mesh,
            self.loss,
            self.metrics,
            self.optimizer,
            comp_mode,
            label_replication=self._label_replication,
            compute_dtype=(
                cfg.compute_dtype if cfg.compute_dtype != "float32" else None
            ),
            remat=cfg.remat,
            pipeline_plan=pipeline_plan,
            wus_axis=(cfg.wus_axis if zero_stage >= 1 else None),
            zero_stage=zero_stage,
            hier_axis=hier_axis,
            remat_segments=remat_plan,
        )
        # per-leaf fallback observability: parallel/zero.py falls back
        # to the replicated update leaf-by-leaf — count it instead of
        # staying silent (the count also rides search_stats)
        if self.executor.zero_stage >= 1:
            fallback = self.executor.zero_fallback_leaves()
            if fallback:
                _log.warning(
                    "zero_stage=%d: %d weight leaf(s) fall back to the "
                    "replicated update (no free dim divisible by the "
                    "%r axis): %s",
                    self.executor.zero_stage, len(fallback),
                    cfg.wus_axis, ", ".join(fallback[:8]) + (
                        f", ... {len(fallback) - 8} more"
                        if len(fallback) > 8 else ""
                    ),
                )
            tel.metrics.counter("parallel/zero_fallback_leaves").inc(
                len(fallback)
            )
            stats = getattr(strategy, "search_stats", None)
            if isinstance(stats, dict):
                stats["zero_fallback_leaves"] = len(fallback)
        if comp_mode == CompMode.TRAINING:
            # what the train step's gradient all-reduces carry, and
            # whether the step is jitted with the compiler options that
            # regroup and reschedule them
            # (executor.grad_sync_overlap_options; docs/PERF.md)
            sync_options = self.executor.grad_sync_compiler_options()
            tel.metrics.gauge("parallel/grad_sync_bytes").set(
                self.executor.grad_sync_bytes())
            tel.metrics.gauge("parallel/grad_sync_async").set(
                int(sync_options is not None))
            if sync_options:
                _log.info(
                    "train step compiled for its gradient all-reduces "
                    "with %s",
                    ", ".join(f"{k}={v}" for k, v in sync_options.items()))
        # score hooks live on the FRONTEND ops (the user's handles);
        # strategy application clones the compiled PCG's op objects
        self._cache_ops = [
            op for op in self.layers.topo_order()
            if op.op_type == OperatorType.CACHE
        ]
        # compiled clones by name; trace-time flags synced from the
        # frontend handles (state/ring stays on the frontend op)
        self._compiled_cache = {
            op.name: op for op in self.operators.topo_order()
            if op.op_type == OperatorType.CACHE
        }
        for fop in self._cache_ops:
            cop = self._compiled_cache.get(fop.name)
            if cop is not None:
                cop.use_cached(fop._load_cached)
        for op in self.operators.topo_order():
            op._flash_min_seq = cfg.flash_min_seq
            # keep the live graph in sync with iter_config across
            # compile/recompile (ops are rebuilt, the config persists)
            op._iter_seq_length = self.iter_config.seq_length
        self._step_cache = {}

    # ------------------------------------------------------------------
    # training surface
    # ------------------------------------------------------------------
    def _device_put_batch(self, inputs: Dict[str, np.ndarray], labels: np.ndarray):
        in_sh = self.executor.input_shardings()
        put_inputs = {
            k: jax.device_put(v, in_sh[k]) for k, v in inputs.items()
        }
        # load_cached Cache ops replay their host ring through an extra
        # feed (reference load_cached forward, cache.cc:214-231)
        for fop in self._cache_ops:
            if fop._load_cached:
                cop = self._compiled_cache.get(fop.name)
                if cop is not None:
                    put_inputs[f"__cache__{fop.name}"] = jax.device_put(
                        fop.cached_value(),
                        self.executor.tensor_sharding(cop.inputs[0]),
                    )
        put_labels = jax.device_put(labels, self.executor.label_sharding())
        return put_inputs, put_labels

    def _update_caches(self, m):
        """Fold cache taps into each frontend Cache op's host ring +
        staleness score (reference cache_update, cache.cc:180-231).
        Taps are processed one step LATE: converting this step's tap to
        numpy would block on the device; holding it until the next call
        overlaps the transfer with the next step's compute.  Flush
        points (use_cached, recompile_on_condition) force currency."""
        taps = m.pop("__cache_taps__", None) if isinstance(m, dict) else None
        pending, self._pending_taps = self._pending_taps, taps
        self._apply_taps(pending)
        return m

    def _apply_taps(self, taps):
        if not taps or not self._cache_ops:
            return
        by_name = {op.name: op for op in self._cache_ops}
        for name, v in taps.items():
            op = by_name.get(name)
            if op is not None and not op._is_legacy_score():
                op.update(np.asarray(v))

    def _flush_cache_taps(self):
        pending, self._pending_taps = self._pending_taps, None
        self._apply_taps(pending)

    def use_cached(self, load_cached: bool, name: Optional[str] = None):
        """Toggle Cache ops between passthrough and cached-batch replay
        (reference Cache::use_cached, cache.cc:259); rebuilds the jitted
        step since the flag is a trace-time constant."""
        self._flush_cache_taps()
        hit = False
        for fop in self._cache_ops:
            if name is not None and fop.name != name:
                continue
            hit = True
            fop.use_cached(load_cached)
            cop = self._compiled_cache.get(fop.name)
            if cop is not None:
                cop.use_cached(load_cached)
        if name is not None and not hit:
            raise ValueError(f"no Cache op named {name!r}")
        if self.executor is not None and hit:
            self._step_fn = self.executor.build_step()
            self._eval_fn = self.executor.build_eval_step()
            self._fwd_fn = self.executor.build_forward()
            self._step_cache = {
                self.iter_config.seq_length: (
                    self._step_fn, self._eval_fn, self._fwd_fn,
                )
            }

    def _attention_core_counts(self) -> Dict[str, object]:
        """Args of the `build_step_fns` span: how many attention ops'
        cores take a Pallas kernel / the dense [b, h, s, s] path in the
        step these functions trace, and the kernels' tiling
        (`MultiHeadAttention.core_plan`; off-TPU the flash branch runs
        its jnp twin, which counts as dense)."""
        itemsize = jnp.dtype(self.executor.compute_dtype
                             or jnp.float32).itemsize
        plans = [op.core_plan(itemsize)
                 for op in self.operators.topo_order()
                 if op.op_type == OperatorType.MULTIHEAD_ATTENTION]
        kernels = [p for p in plans if p in ("one_tile", "online")]
        counts = {
            "attn_kernel_ops": len(kernels),
            "attn_dense_ops": sum(p in ("dense", "jnp") for p in plans),
            "attn_tile": "+".join(sorted(set(kernels))),
        }
        chunks = [op.chunk_tokens(op.inputs[0].shape.logical_shape[1])
                  for op in self.operators.topo_order()
                  if op.op_type == OperatorType.KIMI_DELTA_ATTENTION]
        if chunks:  # positions a chunk of the delta rule holds
            counts.update({"kda_chunk_tokens": min(chunks)})
        rules = [op.recurrence_plan(op.inputs[0].shape.logical_shape[1])
                 for op in self.operators.topo_order()
                 if op.op_type in (OperatorType.KIMI_DELTA_ATTENTION,
                                   OperatorType.GATED_DELTA_NET)]
        if rules:  # delta-rule ops that run as the chunked Pallas kernels
            counts.update({"kda_kernel_ops": rules.count("chunked_kernel")})
        experts = [op.product_plan() for op in self.executor.routed_expert_ops]
        if experts:  # which product each routed-expert layer takes
            counts["expert_grouped_ops"] = experts.count("grouped")
            counts["expert_dense_ops"] = experts.count("dense")
        # segments the train step checkpoints, and what the step is
        # first lowered to keep in them (the step's first call says
        # what fit: `train_step`'s `remat_keep`, `remat_kept_bytes`)
        counts.update(self.executor.loop_counts)  # repeated regions
        counts["remat_segments"] = self.executor.remat_segments
        if counts["remat_segments"]:
            counts["remat_keep"] = self.executor.remat_keep
        return counts

    def set_iteration_config(self, seq_length: Optional[int]):
        """FFIterationConfig.seq_length threading (reference
        model.cc:2415-2419): BatchMatmul ops mask positions past
        seq_length on their declared seq dims.  Step functions are
        memoized per seq_length, so alternating bucketed lengths pays
        one trace each, then dict lookups."""
        if seq_length is None or seq_length == self.iter_config.seq_length:
            return
        self.iter_config.seq_length = seq_length
        for op in self.operators.topo_order():
            op._iter_seq_length = seq_length
        cached = self._step_cache.get(seq_length)
        if cached is None:
            with span("build_step_fns", seq_length=seq_length,
                      **self._attention_core_counts()):
                self._step_fn = self.executor.build_step()
                self._eval_fn = self.executor.build_eval_step()
                self._fwd_fn = self.executor.build_forward()
            self._step_cache[seq_length] = (
                self._step_fn, self._eval_fn, self._fwd_fn,
            )
        else:
            self._step_fn, self._eval_fn, self._fwd_fn = cached

    def train_step(self, inputs: Dict[str, np.ndarray], labels: np.ndarray,
                   seq_length: Optional[int] = None):
        """One jitted iteration: forward + loss + backward + metrics + update."""
        self._check_not_decode_graph("train_step()")
        if self.weights_deferred:
            raise RuntimeError(
                "train_step() on a model compiled with defer_weights=True "
                "(it has no optimizer state and no train step)")
        self.set_iteration_config(seq_length)
        step_fn = self._step_fn
        # first=1: this call traces and compiles the step (or loads it
        # from the persistent cache)
        first = step_fn not in self._stepped_fns
        with span("train_step", step=self._train_steps,
                  first=int(first)) as step_span:
            with span("host_transfer"):
                put_inputs, put_labels = self._device_put_batch(inputs, labels)
            with span("train_step.rng_split"):
                self._rng, step_rng = jax.random.split(self._rng)
            # the enqueue AND, once the runtime's queue is full, the wait
            # for room in it: the step itself runs asynchronously
            with span("train_step.dispatch"):
                self._weights, self._opt_state, self._state, m = step_fn(
                    self._weights, self._opt_state, self._state, put_inputs,
                    put_labels, step_rng,
                )
            with span("train_step.caches"):
                m = self._update_caches(dict(m))
            if "__moe__" in m:
                self._report_moe(m.pop("__moe__"))
            if first and getattr(step_fn, "keep", None) is not None:
                # what the checkpointed segments hold, now that the
                # compiled step has been held against the device
                step_span.set(remat_keep=step_fn.keep,
                              remat_kept_bytes=step_fn.kept_bytes)
            if first:
                plans = [op.grouped_product_plan()
                         for op in self.executor.routed_expert_ops
                         if op.product_plan() == "grouped"]
                if plans:  # what the grouped expert products run on
                    product, tiling = (",".join(dict.fromkeys(x))
                                       for x in zip(*plans))
                    step_span.set(experts_product=product,
                                  experts_tiling=tiling)
        if first:
            self._stepped_fns.add(step_fn)
        self._train_steps += 1
        return m

    def _report_moe(self, counts) -> None:
        """The routed-expert layers' counts of this step (an int32 [6]
        device array the step returns: `executor.moe_counts`) are kept;
        the NEWEST earlier step whose counts have arrived is reported
        as a `train_step.moe` child span and added to the
        `train/moe_*` counters.  `is_ready()` asks and never waits, so
        a step whose counts are still in flight reports nothing."""
        pending = self._pending_moe
        pending.append((self._train_steps, counts))
        ready = [i for i, (_, c) in enumerate(pending[:-1]) if c.is_ready()]
        if not ready:
            return
        step, counts = pending[ready[-1]]
        del pending[:ready[-1] + 1]
        pairs, dropped, max_rows, hit, rows_computed, overflow = (
            int(v) for v in np.asarray(counts))
        with span("train_step.moe", step=step, moe_pairs=pairs,
                  moe_dropped=dropped, moe_max_rows=max_rows, moe_hit=hit,
                  moe_rows_computed=rows_computed, moe_overflow=overflow):
            reg = self.telemetry.metrics
            reg.counter("train/moe_steps").inc()
            reg.counter("train/moe_pairs").inc(pairs)
            reg.counter("train/moe_dropped").inc(dropped)
            reg.counter("train/moe_rows_computed").inc(rows_computed)
            reg.counter("train/moe_overflow").inc(overflow)

    def eval_step(self, inputs: Dict[str, np.ndarray], labels: np.ndarray):
        self._check_not_decode_graph("eval_step()")
        put_inputs, put_labels = self._device_put_batch(inputs, labels)
        return self._eval_fn(self._weights, self._state, put_inputs, put_labels)

    def fit(
        self,
        x: Union[np.ndarray, Sequence[np.ndarray], Dict[str, np.ndarray]],
        y: np.ndarray,
        batch_size: Optional[int] = None,
        epochs: Optional[int] = None,
        callbacks: Sequence = (),
        verbose: bool = True,
        shuffle: bool = False,
    ) -> List[PerfMetrics]:
        """Train over numpy data (reference fit loop flexflow_cffi.py:2044-2087),
        batched through SingleDataLoader (prefetched, sharded placement)."""
        from .dataloader import SingleDataLoader

        assert self._step_fn is not None, "call compile() first"
        batch_size = batch_size or self.config.batch_size
        epochs = epochs or self.config.epochs
        input_ops = self.layers.source_ops()
        if isinstance(x, dict):
            x_map = x
        elif isinstance(x, (list, tuple)):
            x_map = {op.name: arr for op, arr in zip(input_ops, x)}
        else:
            x_map = {input_ops[0].name: x}
        loader = SingleDataLoader(self, x_map, y, batch_size=batch_size,
                                  shuffle=shuffle, seed=self.config.seed)
        num_batches = loader.num_batches
        history: List[PerfMetrics] = []
        if self.config.profiling:
            from .profiler import print_profile, profile_operators

            print_profile(profile_operators(self))
        for cb in callbacks:
            cb.on_train_begin(self)
        try:
            return self._fit_loop(
                loader, epochs, callbacks, verbose, batch_size, num_batches,
                history,
            )
        finally:
            # flush in ALL exits: a crashed traced run (the case
            # observability exists for) still writes its artifacts, and
            # an interrupted --profile-steps window stops the profiler
            # (a no-op without a trace_dir)
            self.telemetry.flush()
            # drain checkpoint-manager callbacks (ModelCheckpoint with
            # async_save): queued background saves must land even when
            # the fit loop died before on_train_end ran
            for cb in callbacks:
                drain = getattr(getattr(cb, "manager", None), "drain", None)
                if callable(drain):
                    drain()

    def _fit_loop(self, loader, epochs, callbacks, verbose, batch_size,
                  num_batches, history):
        tel = self.telemetry
        # steps dispatch asynchronously: this is the host's time in
        # train_step (the first one carries the XLA compile), not a
        # step's; device time shows in the epoch's device_drain span
        # and the fidelity record
        dispatch_hist = tel.metrics.histogram("fit/dispatch_ms")
        global_step = 0
        epoch_step_s: List[float] = []  # per-epoch seconds/step
        for epoch in range(epochs):
            pm = PerfMetrics()
            t0 = time.perf_counter()
            batches = iter(loader)
            while True:
                with span("fit.dataloader_wait"):
                    item = next(batches, None)
                if item is None:
                    break
                batch, labels = item
                tel.on_step(global_step)  # jax.profiler window
                ts = time.perf_counter()
                m = self.train_step(batch, labels)
                dispatch_hist.observe((time.perf_counter() - ts) * 1e3)
                global_step += 1
                # device-side accumulation: float(v) here would force a
                # per-step host<->device sync that breaks the donated
                # step chain; PerfMetrics sums on device and converts
                # once per epoch (finalize below)
                pm.accumulate(m)
                for op in self._cache_ops:
                    # legacy model-level score fns poll here; 4-arg
                    # reference-style scorers already ran in train_step
                    fn = getattr(op, "score_fn", None)
                    if fn is not None and op._is_legacy_score():
                        op.update_score(float(fn(self)))
            with span("device_drain", epoch=epoch):
                jax.block_until_ready(jax.tree.leaves(self._weights)[0])
            dt = time.perf_counter() - t0
            pm.finalize()  # the epoch's single metrics host transfer
            throughput = num_batches * batch_size / dt
            if tel.enabled:
                epoch_step_s.append(dt / max(1, num_batches))
                tel.metrics.histogram("fit/epoch_s").observe(dt)
                tel.metrics.gauge("fit/throughput_sps").set(throughput)
                tel.metrics.fold_counters("fit/metrics", {
                    f: getattr(pm, f) for f in PerfMetrics._FIELDS
                })
                tel.metrics.gauge("fit/metrics/accuracy").set(pm.accuracy)
            if verbose:
                print(
                    f"epoch {epoch}: {pm.summary()} "
                    f"ELAPSED TIME = {dt:.4f}s, THROUGHPUT = {throughput:.2f} samples/s"
                )
            history.append(pm)
            for cb in callbacks:
                cb.on_epoch_end(self, epoch, pm)
            if self._stop_training:
                self._stop_training = False
                break
        for cb in callbacks:
            cb.on_train_end(self)
        if epoch_step_s:
            # fidelity record: predicted vs measured step time.  The
            # best epoch is the steady-state measurement (epoch 0 pays
            # the step fn's XLA compile; with a single epoch that cost
            # is in the measurement — noted in the record's source docs)
            from .obs.fidelity import report_fidelity

            report_fidelity(
                self, min(epoch_step_s),
                steps_measured=global_step, source="fit",
            )
        return history  # fit's finally clause flushes the artifacts

    def fit_resilient(
        self,
        x: Union[np.ndarray, Sequence[np.ndarray], Dict[str, np.ndarray]],
        y: np.ndarray,
        num_steps: Optional[int] = None,
        epochs: Optional[int] = None,
        batch_size: Optional[int] = None,
        directory: Optional[str] = None,
        fault_plan=None,
        retry=None,
        resume: bool = False,
    ):
        """`fit` under the resilience supervisor: periodic checkpoints
        (async verified saves with FFConfig.checkpoint_async),
        restore-and-retry on transient failures, SIGTERM/SIGINT
        preemption grace, a hung-step watchdog (step_timeout), and
        elastic re-search + recompile on device loss
        (resilience/supervisor.py; knobs from FFConfig:
        checkpoint_every/checkpoint_keep/checkpoint_async/step_timeout/
        preempt_grace/max_restarts/retry_backoff/nan_policy).
        Step-indexed and unshuffled so an interrupted run replays
        bit-identically on the same mesh.  resume=True continues from
        the directory's newest verified checkpoint — the replacement
        process of a preempted run picks up where the emergency
        checkpoint left off.  Returns a SupervisorReport."""
        from .resilience import TrainingSupervisor

        assert self._step_fn is not None, "call compile() first"
        batch_size = batch_size or self.config.batch_size
        directory = directory or self.config.checkpoint_dir
        if directory is None:
            raise ValueError(
                "fit_resilient needs a checkpoint directory: pass "
                "directory= or set FFConfig.checkpoint_dir/--checkpoint-dir"
            )
        if num_steps is None:
            num_batches = len(y) // batch_size
            num_steps = num_batches * (epochs or self.config.epochs)
        supervisor = TrainingSupervisor(
            self, directory, fault_plan=fault_plan, retry=retry
        )
        return supervisor.run(x, y, num_steps=num_steps,
                              batch_size=batch_size, resume=resume)

    # reference-parity step pieces (model.h:767-811) — all folded into the
    # single jitted step; kept as explicit methods for API compatibility.
    def init_operators(self):
        return None

    def decode_step(self, inputs: Dict[str, np.ndarray]):
        """One incremental-decode forward: runs the compiled graph with
        the current op state and threads the returned state (KV caches +
        positions advance).  Build the graph with decode-mode attention
        (decode_max_seq > 0) and call reset_decode_state() before each
        new sequence batch."""
        if getattr(self, "_decode_fn", None) is None:
            self._decode_fn = self.executor.build_decode_step()
            limits = [
                op._decode_max_seq
                for op in self.operators.topo_order()
                if getattr(op, "_decode_max_seq", 0)
            ]
            self._decode_limit = min(limits) if limits else 0
            self.sync_decode_pos()
        # host-side overflow guard: on device dynamic_update_slice would
        # silently clamp the write index and corrupt the last cache row
        step = max(
            (int(np.asarray(v).shape[1]) for v in inputs.values()
             if np.asarray(v).ndim >= 2), default=1,
        )
        if self._decode_limit and self._decode_pos + step > self._decode_limit:
            raise ValueError(
                f"decode_step past decode_max_seq={self._decode_limit} "
                f"(position {self._decode_pos}); call reset_decode_state() "
                "to start a new sequence"
            )
        put = {
            k: jax.device_put(v, self.executor.input_shardings()[k])
            for k, v in inputs.items()
        }
        logits, self._state = self._decode_fn(self._weights, self._state, put)
        self._decode_pos += step
        return logits

    def sync_decode_pos(self):
        """Rebuild the host-side overflow-guard counter from the device
        cache_pos entries.  Called after any external `_state` swap
        (checkpoint restore, weight transfer) so the decode_step guard
        never trusts a stale shadow counter."""
        pos = 0
        for entries in (self._state or {}).values():
            cp = entries.get("cache_pos")
            if cp is not None:
                arr = np.asarray(cp).reshape(-1)
                if arr.size:
                    pos = max(pos, int(arr[0]))
        self._decode_pos = pos

    def reset_decode_state(self):
        """Zero the decode caches (each op's `cache_entries()` and
        cache_pos, plus the paged-mode block_table/seq_lens) so the next
        decode_step starts a fresh sequence."""
        import jax.numpy as jnp

        caches = {op.name: op.cache_entries()
                  for op in self.operators.topo_order()}
        names = ("cache_pos", "block_table", "seq_lens")
        self._state = {
            op: {
                k: (jnp.zeros_like(v)
                    if k in names or k in caches.get(op, ()) else v)
                for k, v in entries.items()
            }
            for op, entries in self._state.items()
        }
        self._decode_pos = 0

    def _check_not_decode_graph(self, caller: str):
        """Plain forward/eval/train on a decode-mode graph would run
        decode attention but mis-thread the caches — forward/eval drop
        the updates (stale cache_pos=0 forever), train appends every
        step until cache_pos hits decode_max_seq and the write silently
        clamps.  The flag is a graph invariant, computed once."""
        flag = getattr(self, "_is_decode_graph", None)
        if flag is None:
            flag = self._is_decode_graph = any(
                getattr(op, "_decode_max_seq", 0)
                for op in self.operators.topo_order()
            )
        if flag:
            raise RuntimeError(
                f"{caller} on a decode-mode graph (decode_max_seq > 0) "
                "would discard the KV-cache updates; use decode_step() "
                "(or gpt_generate_cached / gpt_generate_scan)"
            )

    def forward(self, inputs: Dict[str, np.ndarray],
                seq_length: Optional[int] = None):
        self._check_not_decode_graph("forward()")
        self.set_iteration_config(seq_length)
        if self._fwd_fn is None:
            self._fwd_fn = self.executor.build_forward()
        put = {
            k: jax.device_put(v, self.executor.input_shardings()[k])
            for k, v in inputs.items()
        }
        for fop in self._cache_ops:
            if fop._load_cached:
                cop = self._compiled_cache.get(fop.name)
                if cop is not None:
                    put[f"__cache__{fop.name}"] = jax.device_put(
                        fop.cached_value(),
                        self.executor.tensor_sharding(cop.inputs[0]),
                    )
        return self._fwd_fn(self._weights, self._state, put)

    def zero_gradients(self):
        return None  # gradients are functional; nothing to zero

    def backward(self):
        raise RuntimeError(
            "backward is fused into train_step under jax.grad; call train_step"
        )

    def update(self):
        return None

    def recompile(self, strategy=None, devices=None):
        """Re-run compile under a new Strategy/device set, carrying the
        trained weights and optimizer state across (RecompileState's
        alter-hook workhorse; reference model.cc:2422-2427).  Weights
        transfer by op/weight name; shapes must be unchanged."""
        saved_w = self.get_weights()
        saved_opt = jax.tree.map(np.asarray, self._opt_state)
        saved_state = jax.tree.map(np.asarray, self._state)
        saved_rng = self._rng  # mid-training stream must not restart
        args = self._compile_args
        self.compile(
            optimizer=self.optimizer,
            loss_type=args["loss_type"],
            metrics=args["metrics"],
            comp_mode=args["comp_mode"],
            strategy=strategy,
            devices=devices if devices is not None else args["devices"],
        )
        self.set_weights(saved_w)
        # optimizer slots mirror the weight tree (SGD v, Adam m/v), so
        # a pipeline<->per-op strategy change re-maps them through the
        # same layout adaptation; scalar entries (Adam t) pass through
        saved_opt = {
            k: self._adapt_weight_layout(sub) if isinstance(sub, dict)
            else sub
            for k, sub in saved_opt.items()
        }
        self._opt_state = device_put_like(saved_opt, self._opt_state)
        self._state = device_put_like(saved_state, self._state)
        self._rng = saved_rng

    def recompile_on_condition(self, r) -> bool:
        """Fire r.alter() when r.trigger() holds (model.cc:2422)."""
        from .recompile import recompile_on_condition

        self._flush_cache_taps()  # triggers read current cache scores
        return recompile_on_condition(self, r)

    def set_learning_rate(self, lr: float):
        """Change the optimizer lr; rebuilds the jitted step (lr is a
        trace-time constant — the rebuild hits XLA's compile cache for
        previously-seen values)."""
        self.optimizer.set_lr(lr)
        if self.executor is not None:
            self._step_fn = self.executor.build_step()
            self._eval_fn = self.executor.build_eval_step()
            self._fwd_fn = self.executor.build_forward()
            # step fns traced under the old lr are stale
            self._step_cache = {
                self.iter_config.seq_length: (
                    self._step_fn, self._eval_fn, self._fwd_fn,
                )
            }

    # -- weight access (reference get_tensor/set_tensor,
    #    parallel_tensor.cc:650-750) -------------------------------------
    def get_weights(self) -> Dict[str, Dict[str, np.ndarray]]:
        return jax.tree.map(np.asarray, self._weights)

    def _adapt_weight_layout(self, weights):
        """Convert a weight-shaped pytree between the per-op layout and
        the pipeline-stacked layout (the '__pipeline__' group of
        executor.py, keyed '<j>.<name>' with the block dim leading) to
        match the CURRENT executor.  recompile carries trained state by
        op/weight name across strategies; when exactly one side of the
        carry is a PIPELINE strategy the names disagree — this is the
        mapping that makes the carry land (ROADMAP: elastic recompile
        onto a pipeline strategy died on this key mismatch)."""
        plan = getattr(self.executor, "pipeline_plan", None)
        has_stacked = "__pipeline__" in weights
        if (plan is not None) == has_stacked:
            return weights  # layouts already agree
        if plan is not None:
            # per-op -> stacked: gather each template weight across the
            # L blocks onto a leading dim (matches init_weights' layout)
            block_names = {op.name for blk in plan.blocks for op in blk}
            out = {k: dict(v) for k, v in weights.items()
                   if k not in block_names}
            entry = {}
            for j, t_op in enumerate(plan.blocks[0]):
                for spec in t_op.weight_specs:
                    entry[f"{j}.{spec.name}"] = np.stack([
                        np.asarray(weights[blk[j].name][spec.name])
                        for blk in plan.blocks
                    ])
            out["__pipeline__"] = entry
            return out
        # stacked -> per-op: unstack onto the block ops of the current
        # graph (find_repeated_blocks is deterministic on the graph
        # structure, so block order and template op order match the
        # plan that produced the stacked tree)
        from .pcg.segments import find_repeated_blocks

        blocks = find_repeated_blocks(self.layers)
        if not blocks:
            raise ValueError(
                "weights carry a '__pipeline__' group but the current "
                "graph has no repeated block stack to unstack it onto"
            )
        out = {k: dict(v) for k, v in weights.items()
               if k != "__pipeline__"}
        for key, stacked in weights["__pipeline__"].items():
            j_s, wname = key.split(".", 1)
            j = int(j_s)
            arr = np.asarray(stacked)
            if arr.shape[0] != len(blocks):
                raise ValueError(
                    f"stacked weight {key!r} has {arr.shape[0]} block "
                    f"layers but the graph repeats {len(blocks)} blocks"
                )
            for l, blk in enumerate(blocks):
                out.setdefault(blk[j].name, {})[wname] = arr[l]
        return out

    def set_weights(self, weights: Dict[str, Dict[str, np.ndarray]]):
        weights = self._adapt_weight_layout(weights)
        # master layout: the strategy shardings below ZeRO stage 3,
        # the scattered resident layout at stage 3
        shardings = self.executor.master_weight_shardings()
        if self.weights_deferred:
            self._check_deferred_weights(weights)
        with span("serve.set_weights" if self.weights_deferred
                  else "set_weights") as sp:
            # (an array already on its sharding is held, not copied)
            self._weights = jax.tree.map(
                lambda v, s: jax.device_put(jnp.asarray(v), s), weights,
                shardings
            )
            sp.set(bytes=sum(int(v.nbytes)
                             for v in jax.tree.leaves(self._weights)))

    def _check_deferred_weights(self, weights) -> None:
        """A served model takes its weights as given, so what is given
        has to be the whole tree at the right shapes."""
        want = self.executor.abstract_weights()
        for op_name, entries in want.items():
            for k, v in entries.items():
                got = weights.get(op_name, {}).get(k)
                if got is None or tuple(got.shape) != tuple(v.shape):
                    raise ValueError(
                        f"set_weights: {op_name}.{k} wants shape "
                        f"{tuple(v.shape)}, got "
                        f"{None if got is None else tuple(got.shape)}")

    def get_parameter(self, op_name: str, weight_name: str) -> np.ndarray:
        return np.asarray(self._weights[op_name][weight_name])

    # -- layer API, continued.  (Defined last: a line added above
    #    `train_step` can move a kernel's compile-cache key, ROADMAP D16)
    def tied_dense(self, input: ParallelTensor, tied_to: str,
                   name: Optional[str] = None) -> ParallelTensor:
        """A bias-free dense layer whose kernel IS the table of the
        embedding op named `tied_to`, transposed (a tied output head):
        `out_dim` = the table's entries.  One leaf in the weights tree,
        under the embedding's name; `set_weights` / `get_weights` carry
        it once and its gradient is the sum of both uses."""
        from .ops.dense import Embedding

        owner = next((op for op in self.layers.topo_order()
                      if op.name == tied_to), None)
        if not isinstance(owner, Embedding):
            raise ValueError(
                f"tied_dense: {tied_to!r} is not an embedding of this model")
        entries, channels = owner.weights[0].shape.logical_shape
        if input.shape.logical_shape[-1] != channels:
            raise ValueError(
                f"tied_dense: {tied_to}'s table is [{entries}, {channels}] "
                f"and the input's last axis {input.shape.logical_shape[-1]}")
        p = LinearParams(entries, False, ActiMode.NONE, owner.params.dtype)
        return self._add(Linear(p, [input], name=self._name("dense", name),
                                tied_to=tied_to))

    def mamba2_mixer(self, input, params, name=None,
                     slot_state: bool = False):
        """A Mamba-2 mixer (ops/mamba2.py): `params` is a `Mamba2Params`;
        `slot_state` builds the serving twin's op, which carries a conv
        tail and a state-space state a slot."""
        from .ops.mamba2 import Mamba2Mixer

        return self._add(Mamba2Mixer(
            params, [input], name=self._name("mamba2_mixer", name),
            slot_state=slot_state))
