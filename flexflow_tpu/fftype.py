"""Core enums and scalar types for the TPU-native framework.

Mirrors the capability surface of the reference's ffconst.h (see
/root/reference/include/flexflow/ffconst.h:63-160 — 90+ operator types,
loss/metric/parameter-sync enums) but is a fresh, JAX-first design:
dtypes map onto jnp dtypes and operator types are used as keys in the
parallel-computation-graph (PCG) and the substitution/search engines.
"""
from __future__ import annotations

import enum

import jax.numpy as jnp


class DataType(enum.Enum):
    BOOL = "bool"
    INT32 = "int32"
    INT64 = "int64"
    HALF = "float16"
    BF16 = "bfloat16"
    FLOAT = "float32"
    DOUBLE = "float64"

    @property
    def np_dtype(self):
        return jnp.dtype(self.value)

    @property
    def size_bytes(self) -> int:
        return self.np_dtype.itemsize

    @classmethod
    def from_any(cls, value) -> "DataType":
        if isinstance(value, cls):
            return value
        name = jnp.dtype(value).name
        for member in cls:
            if member.value == name:
                return member
        raise ValueError(f"unsupported dtype: {value!r}")


class ActiMode(enum.Enum):
    NONE = "none"
    RELU = "relu"
    SIGMOID = "sigmoid"
    TANH = "tanh"
    GELU = "gelu"


class AggrMode(enum.Enum):
    """Embedding aggregation (reference: AGGR_MODE_* ffconst.h:48-52)."""

    NONE = "none"
    SUM = "sum"
    AVG = "avg"


class PoolType(enum.Enum):
    MAX = "max"
    AVG = "avg"


class LossType(enum.Enum):
    CATEGORICAL_CROSSENTROPY = "categorical_crossentropy"
    SPARSE_CATEGORICAL_CROSSENTROPY = "sparse_categorical_crossentropy"
    MEAN_SQUARED_ERROR_AVG_REDUCE = "mean_squared_error_avg"
    MEAN_SQUARED_ERROR_SUM_REDUCE = "mean_squared_error_sum"
    IDENTITY = "identity"


class MetricsType(enum.Enum):
    ACCURACY = "accuracy"
    CATEGORICAL_CROSSENTROPY = "categorical_crossentropy"
    SPARSE_CATEGORICAL_CROSSENTROPY = "sparse_categorical_crossentropy"
    MEAN_SQUARED_ERROR = "mean_squared_error"
    ROOT_MEAN_SQUARED_ERROR = "root_mean_squared_error"
    MEAN_ABSOLUTE_ERROR = "mean_absolute_error"


class CompMode(enum.Enum):
    TRAINING = "training"
    INFERENCE = "inference"


class ParameterSyncType(enum.Enum):
    """Reference: config.h:55-59 (NONE / PS / NCCL).

    On TPU both PS and NCCL collapse into SPMD gradient psum over the mesh;
    we keep the enum for API parity and to let the simulator model either
    a fused reduce-scatter+all-gather or a plain all-reduce.
    """

    NONE = "none"
    PS = "ps"
    ALL_REDUCE = "all_reduce"  # reference's NCCL path


class OperatorType(enum.Enum):
    # Sources
    INPUT = "input"
    WEIGHT = "weight"
    NOOP = "noop"
    # Dense compute
    CONV2D = "conv2d"
    LINEAR = "linear"
    EMBEDDING = "embedding"
    MULTIHEAD_ATTENTION = "multihead_attention"
    BATCH_MATMUL = "batch_matmul"
    GATED_MLP = "gated_mlp"
    MLA_ATTENTION = "mla_attention"
    ROUTED_EXPERTS = "routed_experts"
    GATED_DELTA_NET = "gated_delta_net"
    KIMI_DELTA_ATTENTION = "kimi_delta_attention"
    SHORT_CONV = "short_conv"
    EVA_ATTENTION = "eva_attention"
    MAMBA2_MIXER = "mamba2_mixer"
    # every pass's output of a repeated region, stacked (pcg LoopRegion)
    LOOP_PASSES = "loop_passes"
    # Elementwise
    ELEMENT_BINARY = "element_binary"
    ELEMENT_UNARY = "element_unary"
    # Normalization / pooling
    POOL2D = "pool2d"
    BATCH_NORM = "batch_norm"
    LAYER_NORM = "layer_norm"
    RMS_NORM = "rms_norm"
    SOFTMAX = "softmax"
    # Shape
    CONCAT = "concat"
    SPLIT = "split"
    FLAT = "flat"
    RESHAPE = "reshape"
    TRANSPOSE = "transpose"
    REVERSE = "reverse"
    PAD = "pad"
    # Reductions / misc
    REDUCE_SUM = "reduce_sum"
    MEAN = "mean"
    CAST = "cast"
    DROPOUT = "dropout"
    GATHER = "gather"
    # MoE quartet (+ cache)
    TOPK = "topk"
    GROUP_BY = "group_by"
    AGGREGATE = "aggregate"
    AGGREGATE_SPEC = "aggregate_spec"
    CACHE = "cache"
    # Recurrent (reference legacy nmt/ LSTM)
    LSTM = "lstm"
    # Size-changing replication/reduction in the reference's convention
    # (replicate.cc:74-75 size *= degree; reduction.cc:74-77 size /=
    # degree): d stacked copies along a dim / fold-sum of d slices.
    # Compute ops here (NOT in the parallel set — our strategy IR's
    # Replicate/Reduction use the implicit replica dim instead); used by
    # the TASO catalog rules (pcg/taso.py).
    REPLICATE_STACK = "replicate_stack"
    REDUCTION_FOLD = "reduction_fold"
    # Fusion
    FUSED = "fused"
    # Parallel ops (the parallelism IR, reference src/parallel_ops/)
    REPARTITION = "repartition"
    COMBINE = "combine"
    REPLICATE = "replicate"
    REDUCTION = "reduction"
    ALLTOALL = "all_to_all"  # TPU-native addition for SP/EP resharding
    PIPELINE = "pipeline"
    FUSED_PARALLEL = "fused_parallel"

    def is_parallel_op(self) -> bool:
        return self in _PARALLEL_OPS


_PARALLEL_OPS = frozenset(
    {
        OperatorType.REPARTITION,
        OperatorType.COMBINE,
        OperatorType.REPLICATE,
        OperatorType.REDUCTION,
        OperatorType.ALLTOALL,
        OperatorType.PIPELINE,
        OperatorType.FUSED_PARALLEL,
    }
)


class OpUnary(enum.Enum):
    EXP = "exp"
    LOG = "log"
    SIN = "sin"
    COS = "cos"
    RELU = "relu"
    GELU = "gelu"
    SIGMOID = "sigmoid"
    TANH = "tanh"
    ELU = "elu"
    IDENTITY = "identity"
    RSQRT = "rsqrt"
    SQRT = "sqrt"
    ERF = "erf"
    FLOOR = "floor"
    POW = "pow"
    SCALAR_MULTIPLY = "scalar_multiply"
    SCALAR_ADD = "scalar_add"
    SCALAR_SUB = "scalar_sub"
    SCALAR_TRUE_DIV = "scalar_true_div"
    NEGATIVE = "negative"


class OpBinary(enum.Enum):
    ADD = "add"
    SUB = "sub"
    MUL = "mul"
    DIV = "div"
    MAX = "max"
    MIN = "min"
    POW = "pow"
