"""Base operator class for the PCG.

Fresh design replacing the reference's `Op` base
(/root/reference/include/flexflow/operator.h:51-196) and its 4-part
per-op pattern (params struct / graph-time ctor / Legion launches /
CUDA task bodies — exemplar src/ops/linear.cc).  Here each op is:

  1. a frozen **params dataclass** (hashable — node-dedup key for the
     search, like linear_params.h + model.h:676-704 get_or_create_node);
  2. a **shape rule** `infer_output_shapes` that propagates both logical
     sizes and partition degrees (replacing the reference's
     parallel-dim-mapping records, operator.h:53-121);
  3. a pure **jax forward** `forward(...)` on logical (global) arrays —
     XLA SPMD shards it according to the tensors' machine views, and
     `jax.grad` supplies backward (no hand-written backward tasks);
  4. **cost hooks** (`flops`, `memory_bytes`) consumed by the simulator
     in place of cudaEvent timing (model.cu:38-75).

Op-level parallelism choices that the reference expresses through each
op's MachineView + weight replica dims (e.g. linear out-channel
partition, attention head partition, embedding vocab partition) live in
a per-op `ShardConfig`, mutated by the strategy search.
"""
from __future__ import annotations

import dataclasses
from typing import (Any, Callable, Dict, List, Optional, Sequence,
                    Tuple)

import jax
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..fftype import DataType, OperatorType
from ..initializer import Initializer
from ..tensor import ParallelTensor, ParallelTensorShape

_op_guid = [2000]

#: the one name a value is tagged with to stay alive across a
#: checkpointed segment (`GraphExecutor`'s `remat` policy saves it
#: beside the segment's matrix products)
REMAT_KEPT = "remat_kept"


def remat_keep(x):
    """Tag `x` as dear to recompute and cheap to hold: a kernel's or a
    grouped product's output, which a policy on `dot_general` cannot
    see.  The op only names the value; whether a checkpointed segment
    keeps it is the executor's choice.  Outside `jax.checkpoint` the
    tag is the identity."""
    return checkpoint_name(x, REMAT_KEPT)


@dataclasses.dataclass(frozen=True)
class ShardConfig:
    """Op-internal parallelism degrees (strategy-search mutable).

    channel: shard the op's weight/output channel dim (linear out-channels,
        attention heads via head_degree alias, conv out-channels).
    reduction: shard the contraction dim (linear in-channels) — output
        becomes partial-sum with replica degree = reduction; a Reduction
        parallel op (or XLA's automatic all-reduce under SPMD) collapses it.
    attribute: shard an attribute dim (embedding vocab, conv in-channel
        attribute parallelism; reference --enable-attribute-parallel).
    expert: expert parallelism degree for MoE ops.
    """

    channel: int = 1
    reduction: int = 1
    attribute: int = 1
    expert: int = 1

    def is_trivial(self) -> bool:
        return self.channel == self.reduction == self.attribute == self.expert == 1


@dataclasses.dataclass(frozen=True)
class WeightSpec:
    name: str
    shape: ParallelTensorShape
    initializer: Optional[Initializer] = None


@dataclasses.dataclass(frozen=True)
class DispatchGroup:
    """What the ops of one `Op.dispatch_group()` tell a paged twin
    (`Op.dispatch_group_of`; docs/SERVING.md "What a mixer with serving
    state declares")."""

    #: the group's fixed facts, a layer's or summed as the ops choose;
    #: the twin adds `layers` and `state_bytes`, `stats()[group]` holds
    #: them beside the sums
    geometry: Dict[str, int]
    #: `counts(positions, counts, chunk)`: the args of the span of a
    #: dispatch that advances row i over `positions[i] .. + counts[i] -
    #: 1` (numpy, [slots]) in a program of `chunk` tokens a row, summed
    #: by program in `stats()[group]`.  Host arithmetic on host-owned
    #: lengths: no fetch, no argument to a program
    counts: Callable[[Any, Any, int], Dict[str, int]]
    #: args of the twin's `serve.build_twin` span
    build_args: Dict[str, int] = dataclasses.field(default_factory=dict)
    #: gauges `serving/<name>`, set once an engine
    gauges: Dict[str, int] = dataclasses.field(default_factory=dict)


class Op:
    """A node in the parallel computation graph."""

    op_type: OperatorType = OperatorType.NOOP

    def __init__(
        self,
        params,
        inputs: Sequence[ParallelTensor],
        name: str = "",
        shard: ShardConfig = ShardConfig(),
    ):
        _op_guid[0] += 1
        self.guid = _op_guid[0]
        self.params = params
        self.inputs: List[ParallelTensor] = list(inputs)
        self.shard = shard
        self.name = name or f"{self.op_type.value}_{self.guid}"
        self.machine_view = None  # assigned by strategy lowering
        # Shape inference + weight/output creation
        out_shapes = self.infer_output_shapes([t.shape for t in inputs])
        self.outputs: List[ParallelTensor] = [
            ParallelTensor(s, owner_op=self, owner_idx=i, name=f"{self.name}.out{i}")
            for i, s in enumerate(out_shapes)
        ]
        self.weight_specs: List[WeightSpec] = self.make_weight_specs(
            [t.shape for t in inputs]
        )
        self.weights: List[ParallelTensor] = [
            ParallelTensor(ws.shape, owner_op=self, owner_idx=i,
                           name=f"{self.name}.{ws.name}")
            for i, ws in enumerate(self.weight_specs)
        ]

    # -- to override ----------------------------------------------------
    def ctor_kwargs(self) -> dict:
        """Extra constructor kwargs a reconstruction must pass.  Ops are
        re-instantiated as type(op)(params, inputs, name=, shard=,
        **ctor_kwargs()) by apply_strategy / clone_op / search variant
        enumeration; ops carrying construction-time flags beyond
        (params, shard) override this (MultiHeadAttention decode mode)."""
        return {}

    def infer_output_shapes(
        self, input_shapes: Sequence[ParallelTensorShape]
    ) -> List[ParallelTensorShape]:
        raise NotImplementedError

    def make_weight_specs(
        self, input_shapes: Sequence[ParallelTensorShape]
    ) -> List[WeightSpec]:
        return []

    def forward(
        self,
        inputs: Sequence[jax.Array],
        weights: Sequence[jax.Array],
        *,
        training: bool = False,
        rng: Optional[jax.Array] = None,
    ) -> List[jax.Array]:
        raise NotImplementedError

    # -- cost hooks (simulator) -----------------------------------------
    def flops(self) -> float:
        """Forward FLOPs for one full (unsharded) application."""
        return 0.0

    # -- what the executor and the serving tier ask of an op ------------
    #: weights the executor hands over in their stored dtype whatever
    #: `compute_dtype` says (a router whose top-k flips under bf16)
    float32_weights: Tuple[str, ...] = ()

    def cache_entries(self) -> Tuple[str, ...]:
        """Names of this op's state entries that hold cached keys,
        values or latents.  They live in the compute dtype; on a paged
        twin each is a `[num_blocks, page, ...]` pool addressed by the
        host-owned `block_table`, so block bytes, copy-on-write and
        block export ask here instead of spelling entry names."""
        return ()

    def slot_state_entries(self) -> Tuple[str, ...]:
        """Names of this op's state entries that are PER SLOT AND FIXED
        SIZE (`[slots, ...]`: a recurrent layer's state, not a cache
        that grows with the sequence).  The serving tier zeroes a
        slot's rows at admission and frees them with the slot; nothing
        that walks `cache_entries()` (block bytes, copy-on-write,
        export, beam reordering) sees them.  They live in the compute
        dtype unless `float32_weights` names them; the host-owned
        `row_tokens` entry beside them says how many of a step's tokens
        each row really advances by."""
        return ()

    #: whether the scheduler zeroes `slot_state_entries()` when it gives
    #: the slot to a request.  False for state that is masked by the
    #: sequence's own positions (`ops/eva_attention.py`): nothing of the
    #: last tenant can be read, so there is nothing to zero
    slot_state_resets: bool = True

    def dispatch_group(self) -> Optional[str]:
        """The name under which this op, as built, tells the serving
        tier what a dispatch costs it ("swa": a window layer's ring;
        None: nothing to tell).  The ops of one name answer together,
        once a twin (`dispatch_group_of`): the serving tier knows the
        names it is handed and no op's."""
        return None

    @classmethod
    def dispatch_group_of(cls, ops: Sequence["Op"], *, family: str,
                          batch_slots: int, page_size: int, max_seq: int,
                          prefill_chunk: int,
                          state_bytes: int) -> "DispatchGroup":
        """What `ops`, a paged twin's ops of one `dispatch_group()` in
        graph order, tell that twin at its build: `batch_slots` rows of
        up to `max_seq` positions in pages of `page_size`, step programs
        of 1 and of `prefill_chunk` tokens a row (0: the step alone),
        `state_bytes` in the ops' `slot_state_entries()`.  A
        `prefill_chunk` the ops cannot take is a `ConfigError` here,
        naming `family`.  Asked of the ops together because some of
        what they count is one layer's and some a sum over them."""
        raise NotImplementedError(
            f"{cls.__name__} names a dispatch group and does not "
            "describe it")

    #: planes each of `cache_entries()` holds: one, or one per pass of
    #: the region that runs the op (`pcg.graph.LoopRegion`).  A paged
    #: pool of N planes is ONE array `[N x num_blocks, page, ...]`, and
    #: block b of a sequence's table is N pages, one a plane, at rows
    #: `t x num_blocks + b`
    cache_planes: int = 1

    def loop_state(self, entries: Dict[str, jax.Array], step
                   ) -> Dict[str, jax.Array]:
        """This op's state entries as pass `step` (traced) of the
        region that runs it sees them.  The executor hands the op that
        view and keeps, of what the op returns, only the entries the
        view left as they were: an op that caches points its table at
        the pass's plane here, and the table it hands back is dropped."""
        return entries

    def memory_bytes(self) -> int:
        total = sum(t.shape.size_bytes() for t in self.outputs)
        total += sum(w.shape.size_bytes() for w in self.weights)
        return total

    def is_parallel_op(self) -> bool:
        return self.op_type.is_parallel_op()

    # -- search support --------------------------------------------------
    def with_shard(self, shard: ShardConfig) -> "ShardConfig":
        return shard

    def node_key(self) -> Tuple:
        """Hashable dedup key (reference get_or_create_node, model.h:676)."""
        return (
            self.op_type,
            self.params,
            self.shard,
            tuple(t.shape for t in self.inputs),
        )

    def __repr__(self) -> str:
        ins = ",".join(str(t.shape) for t in self.inputs)
        outs = ",".join(str(t.shape) for t in self.outputs)
        return f"{self.name}({ins} -> {outs})"

    def borrowed_weights(self) -> Tuple[Tuple[str, str], ...]:
        """(op name, weight name) of every trainable weight this op
        READS and another op OWNS (a head tied to the embedding's
        table).  The executor hands them over BEFORE the op's own, in
        this order; the leaf stays one leaf of the weights tree, under
        its owner's name, and its gradient is jax's sum over its
        readers."""
        return ()


# ---------------------------------------------------------------------------
# Shared shape-rule helpers
# ---------------------------------------------------------------------------

def elementwise_shape(
    shape: ParallelTensorShape, dtype: Optional[DataType] = None
) -> ParallelTensorShape:
    return ParallelTensorShape(shape.dims, dtype or shape.dtype)


def check_no_partition(shape: ParallelTensorShape, dim_idx: int, opname: str):
    dims = [d for d in shape.dims if not d.is_replica_dim]
    if dims[dim_idx].degree != 1:
        raise ShapeError(
            f"{opname}: dim {dim_idx} (size {dims[dim_idx].size}) may not be "
            f"partitioned (degree {dims[dim_idx].degree})"
        )


class ShapeError(ValueError):
    """Raised when an op cannot accept the given input parallel shapes —
    the search treats this as an illegal strategy candidate."""


def trainable_weight_count(op: Op) -> int:
    """Weights [0:n] are trainable; the rest are op state (BatchNorm
    running stats).  Ops opt in via a num_trainable_weights method."""
    fn = getattr(op, "num_trainable_weights", None)
    return fn() if fn is not None else len(op.weight_specs)


def rstate_group(ops: Sequence[Op], takes_kernel, prefix: str, *,
                 batch_slots: int, prefill_chunk: int,
                 state_bytes: int) -> DispatchGroup:
    """The `rstate` group of ops whose per-slot state is a recurrence's
    (`GatedDeltaNet`, `Mamba2Mixer`): `rstate_rows_live`, the rows a
    dispatch had to advance, against `rstate_rows_touched`, the rows
    whose state the program of that step length read and wrote: the
    advanced rows where `takes_kernel(op, step_tokens)` holds for every
    layer (a kernel that skips idle rows; asked once for each step
    length the twin runs), every slot otherwise.  `<prefix>_kernel_ops`
    layers take the kernel in every step program, `<prefix>_plain_ops`
    the plain recurrence in some."""
    lengths = (1, *((prefill_chunk,) if prefill_chunk else ()))
    in_kernel = [[takes_kernel(op, s) for s in lengths] for op in ops]
    skips_idle = {s: all(op[i] for op in in_kernel)
                  for i, s in enumerate(lengths)}
    kernels = sum(all(op) for op in in_kernel)
    built = {f"{prefix}_kernel_ops": kernels,
             f"{prefix}_plain_ops": len(ops) - kernels}

    def counts(positions, counts, chunk):
        live = len([n for n in counts if n])
        return {"rstate_rows_live": live,
                "rstate_rows_touched": (live if skips_idle.get(chunk)
                                        else batch_slots)}

    return DispatchGroup(geometry=built, counts=counts,
                         build_args={"rstate_bytes": state_bytes, **built})
