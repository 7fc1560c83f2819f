"""Simulator: predicts step time + memory for a strategy-applied PCG.

Reference: src/runtime/simulator.cc — task-graph event simulation
(simulate_runtime :822-1250), per-op cost measurement with a
(params, view) cache (:537-578, model.cu:38-75 cudaEvent timing), comm
cost estimators (estimate_xfer_cost :622-767, sync cost :786-813), and
the fork's topology-routed variant (:1251-1800).

TPU-native redesign: our execution model is SPMD — every device runs the
same jitted program — so the per-device timeline is the SAME sequence of
(sharded) compute ops and collectives.  The simulator therefore costs:

  step = sum_ops max-shard compute (fwd [+ bwd])
       + sum resharding collectives (the parallel ops)
       + partial-sum reductions (contraction-dim sharding)
       + gradient all-reduce over each weight's replica axes
       - a compute/comm overlap credit (XLA latency hiding)

Compute costs come from an analytic roofline (flops/peak, bytes/HBM-bw)
calibrated by optional real measurements (measure_fn timing jitted ops
on the actual chip — the analogue of inner_measure_operator_cost), with
the same (node_key, view)->cost cache as the reference.  Memory is
accounted per device: weight + optimizer-slot + gradient shards plus
peak live activations — feeding the memory-aware search
(memory_optimization.h:45-70 equivalent).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..fftype import OperatorType
from ..ops.op import Op
from ..pcg.graph import Graph
from .machine_model import MachineModel, TpuPodModel
from ..topology.comm import CommCost, ZERO_COST, ring_bytes


@dataclasses.dataclass
class CostMetrics:
    """Per-op cost record (reference CostMetrics simulator.h)."""

    forward_time: float = 0.0
    backward_time: float = 0.0
    sync_time: float = 0.0
    inputs_memory: int = 0
    outputs_memory: int = 0
    weights_memory: int = 0


class SimResult:
    """Simulation outcome.

    `per_device_memory` is LAZY: searches only consume it when a memory
    budget is set, but the liveness/remat scan behind it used to be paid
    on every evaluation.  Constructing with `memory_fn` defers the scan
    to first access (the computed value is then cached); constructing
    with an int keeps the eager behavior.
    """

    def __init__(
        self,
        total_time: float,
        compute_time: float,
        comm_time: float,
        sync_time: float,
        per_device_memory: Optional[int] = None,
        breakdown: Optional[Dict[str, float]] = None,
        memory_fn: Optional[Callable[[], int]] = None,
    ):
        self.total_time = total_time
        self.compute_time = compute_time
        self.comm_time = comm_time
        self.sync_time = sync_time
        self.breakdown = breakdown if breakdown is not None else {}
        self._memory = per_device_memory
        self._memory_fn = memory_fn
        # per-tier comm split (topology subsystem): simulate_ops fills
        # it from the OpTerms ici_/dcn_ fields; zero on flat meshes
        self.comm_tiers: Dict[str, float] = {
            "ici_time": 0.0, "dcn_time": 0.0,
            "ici_bytes": 0.0, "dcn_bytes": 0.0,
        }
        # searched-remat telemetry (mem/activation_bytes,
        # compute/recompute_s): saved-activation bytes under the costed
        # plan and the recompute seconds the plan charges; simulate_ops
        # fills them (recompute_s is 0 for dense / legacy-bool runs)
        self.activation_bytes: float = 0.0
        self.recompute_s: float = 0.0

    @property
    def per_device_memory(self) -> int:
        if self._memory is None:
            self._memory = int(self._memory_fn()) if self._memory_fn else 0
            self._memory_fn = None  # release the captured op sequence
        return self._memory


@dataclasses.dataclass(frozen=True)
class OpTerms:
    """One op's additive contribution to a simulation — the delta-sim
    decomposition (reference delta simulation in simulate_runtime: after
    an MCMC substitution only affected tasks re-simulate).  Every field
    depends ONLY on the cache key — node_key (op type, params,
    ShardConfig, input parallel shapes), mesh signature, training — so
    terms are cached across candidate strategies and whole-graph totals
    are re-aggregated from cache."""

    compute: float = 0.0      # analytic fwd(+bwd) time, pre compute_scale
    xfer: float = 0.0         # parallel-op resharding collective
    partial: float = 0.0      # fwd partial-sum all-reduce (undoubled)
    grad_sync: float = 0.0    # gradient sync over weight replica axes
    #                           (all-reduce; reduce-scatter at stage >= 1)
    opt_numel: float = 0.0    # master-precision elements the update touches
    #                           (already /rep under the sharded update)
    opt_xfer: float = 0.0     # post-update weight all-gather (stage 1/2)
    gather_xfer: float = 0.0  # ZeRO-3 per-layer weight all-gathers
    #                           (fwd + bwd re-gather; prefetch-credited)
    ici_xfer: float = 0.0     # per-tier (uncredited) split of ALL the
    dcn_xfer: float = 0.0     # op's comm seconds: intra-slice ICI vs
    #                           inter-slice DCN (flat mesh = all ICI);
    #                           grad/opt legs fold in only when training
    ici_bytes: float = 0.0    # per-device ring bytes over each tier —
    dcn_bytes: float = 0.0    # the comm/{ici,dcn}_bytes telemetry split
    mem_weights: int = 0      # per-device weight shard bytes (compute copy)
    mem_master: int = 0       # per-device master-resident weight bytes
    #                           (== mem_weights below stage 3; /group at 3)
    mem_grad: int = 0         # per-device gradient buffer bytes
    #                           (== mem_weights below stage 2; /group at 2+)
    mem_gather: int = 0       # stage-3 gathered weight copy bytes for THIS
    #                           op (double-buffer window: 2x the max rides
    #                           the memory total)
    mem_opt: int = 0          # per-device bytes ONE optimizer slot costs
    #                           (== mem_weights replicated; grad weights
    #                           /rep under the sharded update)
    mem_residual: int = 0     # backward-residual activation bytes
    mem_transient: int = 0    # fused transient workspace bytes (max-reduced)
    mem_activation: int = 0   # per-device saved-activation bytes when this
    #                           op's remat segment is OFF (== the dense
    #                           residual term; a remat'd segment drops its
    #                           internals from the step-long residency)
    recompute: float = 0.0    # backward re-execution seconds when the
    #                           op's segment is remat'd: the forward pass
    #                           runs again inside backward (compute + fwd
    #                           collectives; at ZeRO-3 the re-gather loses
    #                           its double-buffered prefetch credit)


_KERNEL_OVERHEAD = 2e-6  # per-op dispatch/fusion overhead (XLA fuses, small)

#: semantic version of the analytic cost model + simulator formulas.
#: Part of the strategy store's simulator-version key component
#: (store/key.py): bump it whenever cost semantics change — OpTerms
#: decomposition, comm estimators, overlap crediting, memory accounting
#: — so strategies searched under the old model stop hitting and
#: re-search under the new one instead of replaying stale rankings.
#: (The learned cost model, arXiv:2008.01040, will ride this same
#: constant: model retrain => version bump => fleet-wide invalidation.)
#: v2: the ZeRO ladder — OpTerms grew mem_master/mem_grad/mem_gather/
#: gather_xfer and the memory/update accounting became zero_stage-aware,
#: so stage-blind v1 rankings must re-search.  A tier-1 guard test pins
#: the OpTerms field set to this number (tests/test_zero_ladder.py):
#: changing the decomposition without bumping here fails CI.
#: v3: the multi-slice topology subsystem (docs/TOPOLOGY.md) — OpTerms
#: grew the ici_xfer/dcn_xfer/ici_bytes/dcn_bytes per-tier split, comm
#: estimators became placement-aware (a collective crossing the slice
#: boundary costs the hierarchical / DCN form), and the sharded-update
#: group shrinks to the intra-slice remainder under a cross-slice
#: placement — slice-blind v2 rankings must re-search.
#: v4: searched rematerialization (docs/PERF.md "Searched
#: rematerialization") — OpTerms grew mem_activation/recompute, remat
#: became a per-segment plan both searches cost under --memory-search,
#: and DCN grad-sync latency is bucket-amortized (--dcn-bucket-mb) on
#: hierarchy machines — remat-blind v3 rankings must re-search.
COST_MODEL_VERSION = 4

#: per-candidate cap on the segments the searches treat as independent
#: remat decisions; plans may still name higher indices (ignored past
#: the graph's actual segment count)
MAX_REMAT_SEGMENTS = 24

#: default DCN grad-sync coalescing bucket (bytes): real runtimes bucket
#: grad all-reduces (~25MB), so the per-leaf DCN latency term amortizes
#: over the bucket a leaf rides in instead of being paid per leaf
DEFAULT_DCN_BUCKET_BYTES = 25 * 2**20

#: overlap credit for the ZeRO-3 per-layer weight all-gathers: the
#: executor double-buffers (layer k+1's gather issues before layer k's
#: compute), but the gathers sit on the layer-boundary critical path, so
#: they hide WORSE than generic resharding collectives.  This replaces
#: the generic overlap_fraction credit for what used to be opt_xfer:
#: 2 gathers/step at (1 - 0.5) exposed always costs more than stage 1's
#: single post-update gather at the generic credit, which is what keeps
#: unconstrained searches on stages <= 1.
Z3_PREFETCH_OVERLAP = 0.5

# backward/forward cost ratio per op class (replaces the old flat 2x:
# conv/matmul backward really is two same-size contractions, but an
# embedding backward is one gradient scatter with no input grad, and
# elementwise/pool/softmax backward is a single pass like forward)
_BWD_RATIO_DEFAULT = 2.0
_BWD_RATIO = {
    OperatorType.CONV2D: 2.0,
    OperatorType.LINEAR: 2.0,
    OperatorType.BATCH_MATMUL: 2.0,
    # flash backward recomputes scores in both the dq and dkv kernels
    OperatorType.MULTIHEAD_ATTENTION: 2.5,
    OperatorType.EMBEDDING: 1.0,
    OperatorType.BATCH_NORM: 1.5,
    OperatorType.LAYER_NORM: 1.5,
    OperatorType.POOL2D: 1.0,
    OperatorType.SOFTMAX: 1.0,
    OperatorType.DROPOUT: 1.0,
    OperatorType.CAST: 1.0,
    OperatorType.ELEMENT_UNARY: 1.0,
    OperatorType.ELEMENT_BINARY: 1.0,
    OperatorType.CONCAT: 1.0,
    OperatorType.SPLIT: 1.0,
    OperatorType.FLAT: 0.5,
    OperatorType.RESHAPE: 0.5,
    OperatorType.TRANSPOSE: 1.0,
}


def backward_ratio(op: Op) -> float:
    return _BWD_RATIO.get(op.op_type, _BWD_RATIO_DEFAULT)


class OpCostModel:
    """(node_key)->cost cache with analytic roofline + measured override.

    measure_fn, when provided, times the real jitted op on hardware and
    its result replaces the analytic estimate (reference
    inner_measure_operator_cost, model.cu:38-75, with the same
    (params, view)->cost cache, simulator.cc:550-560).  Measured results
    additionally persist to `cache_path` as JSON so later searches —
    even in fresh processes — reuse chip timings without re-profiling.
    """

    #: ops cheaper than this many FLOPs keep the analytic estimate —
    #: their cost is dispatch-dominated and profiling each candidate
    #: view would cost far more than the information is worth
    MEASURE_MIN_FLOPS = 5e6

    def __init__(
        self,
        machine: MachineModel,
        measure_fn: Optional[Callable[[Op], Optional[float]]] = None,
        compute_dtype_bytes: int = 2,  # bf16
        cache_path: Optional[str] = None,
        device_key: str = "",
    ):
        self.machine = machine
        self.measure_fn = measure_fn
        self.cache: Dict[Tuple, CostMetrics] = {}
        self.dtype_bytes = compute_dtype_bytes
        self.cache_path = cache_path
        # measured times are chip-specific: namespace persisted keys by
        # the device kind so a cache calibrated on one backend is never
        # replayed on another
        self.device_key = device_key
        self.measured_hits = 0  # cost() calls answered by a measurement
        self.cost_hits = 0      # cost() calls answered by the node_key cache
        # where those measurements came from: timed on the live backend
        # by THIS search, or read back from the persisted cache file
        self.measured_fresh = 0
        self.measured_replayed = 0
        self.measure_seconds = 0.0  # wall time spent inside measure_fn
        self._persistent: Dict[str, float] = {}
        self._dirty = False
        if cache_path:
            self.load_persistent(cache_path)

    # -- measured-cost persistence --------------------------------------
    def load_persistent(self, path: str):
        import json
        import os

        if os.path.exists(path):
            try:
                with open(path) as f:
                    data = json.load(f)
                self._persistent.update(
                    {k: float(v) for k, v in data.items()}
                )
            except (OSError, ValueError, TypeError, AttributeError):
                pass  # absent, torn, or valid-JSON-wrong-shape

    def save_persistent(self, path: Optional[str] = None):
        """Crash-safe, concurrency-safe persistence of the measured-cost
        cache.  Called unconditionally at the end of every Unity/MCMC
        search (unity.py/mcmc.py), so a mid-write kill must never
        corrupt the shared file: the write goes to a process-unique tmp
        (mkstemp — a fixed `.tmp` name would let two searches clobber
        each other's staging) and lands via one atomic os.replace.
        Merge-on-save: entries measured by OTHER concurrent searches
        since our load are re-read and kept — last writer no longer
        erases them; our own measurements win ties."""
        import json
        import os
        import tempfile

        path = path or self.cache_path
        if not path or not self._dirty:
            return
        path = os.path.abspath(path)
        dirname = os.path.dirname(path)
        os.makedirs(dirname, exist_ok=True)
        merged: Dict[str, float] = {}
        try:
            with open(path) as f:
                merged = {k: float(v) for k, v in json.load(f).items()}
        except (OSError, ValueError, TypeError, AttributeError):
            # absent, torn, or valid-JSON-wrong-shape (a list, null
            # values) — our entries still publish whole either way
            merged = {}
        merged.update(self._persistent)
        fd, tmp = tempfile.mkstemp(
            dir=dirname, prefix=os.path.basename(path) + ".tmp-"
        )
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(merged, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        self._persistent = merged
        self._dirty = False

    def cost(self, op: Op) -> CostMetrics:
        key = op.node_key()
        hit = self.cache.get(key)
        if hit is not None:
            self.cost_hits += 1
            return hit
        cm = self._analytic(op)
        measured = self._measured(op, key)
        if measured is not None:
            self.measured_hits += 1
            cm.forward_time = measured
            cm.backward_time = backward_ratio(op) * measured
        self.cache[key] = cm
        return cm

    #: bump when the measurement harness changes semantics — v2: the
    #: r01/r02 chained-scan timing was DCE'd by XLA (barrier split) and
    #: persisted near-zero garbage that must never be replayed
    MEASURE_CACHE_VERSION = 2

    def _measured(self, op: Op, key: Tuple) -> Optional[float]:
        if self.measure_fn is None or op.is_parallel_op():
            return None
        if op.flops() < self.MEASURE_MIN_FLOPS:
            return None
        skey = f"v{self.MEASURE_CACHE_VERSION}|{self.device_key}|{key!r}"
        if skey in self._persistent:
            self.measured_replayed += 1
            return self._persistent[skey]
        t0 = time.perf_counter()
        measured = self.measure_fn(op)
        self.measure_seconds += time.perf_counter() - t0
        if measured is not None:
            self.measured_fresh += 1
            self._persistent[skey] = measured
            self._dirty = True
        return measured

    def _shard_fraction(self, op: Op) -> float:
        """Fraction of the op's total FLOPs done by one device."""
        deg = 1
        for t in op.outputs:
            deg = max(deg, int(np.prod([d.degree for d in t.shape.dims
                                        if not d.is_replica_dim])))
        # contraction-dim sharding also divides flops
        red = max(
            (t.shape.replica_degree for t in op.outputs), default=1
        )
        return 1.0 / max(1, deg * red)

    def _analytic(self, op: Op) -> CostMetrics:
        dev = self.machine.device()
        flops = op.flops() * self._shard_fraction(op)
        in_bytes = sum(t.shape.shard_bytes() for t in op.inputs)
        out_bytes = sum(t.shape.shard_bytes() for t in op.outputs)
        w_bytes = sum(w.shape.shard_bytes() for w in op.weights)
        bytes_moved = in_bytes + out_bytes + w_bytes
        t_compute = flops / dev.peak_flops
        t_mem = bytes_moved / dev.hbm_bandwidth
        fwd = max(t_compute, t_mem) + _KERNEL_OVERHEAD
        return CostMetrics(
            forward_time=fwd,
            backward_time=(
                backward_ratio(op) * fwd if op.weights or op.inputs else 0.0
            ),
            inputs_memory=in_bytes,
            outputs_memory=out_bytes,
            weights_memory=w_bytes,
        )


def make_cost_model(cfg, machine: MachineModel) -> OpCostModel:
    """Build the search's cost model from FFConfig: measured calibration
    (profiler.make_measure_fn) when cfg.should_calibrate(), with the
    measured cache persisted across runs (reference keeps the same
    (params, view)->cost cache for the whole search,
    simulator.cc:550-560)."""
    measure_fn = None
    cache_path = cfg.op_cost_cache_file
    device_key = ""
    if cfg.should_calibrate():
        from ..profiler import make_measure_fn

        import jax

        measure_fn = make_measure_fn()
        device_key = jax.devices()[0].device_kind
        if cache_path is None:
            import os

            cache_path = os.path.join(
                os.path.expanduser("~"), ".cache", "flexflow_tpu",
                "op_costs.json",
            )
    return OpCostModel(machine, measure_fn=measure_fn, cache_path=cache_path,
                       device_key=device_key)


#: ops whose segments can never rematerialize (side effects / host
#: state / routing state) — the shared impurity rule of the searched
#: remat dimension (the executor's _build_remat_plan additionally
#: excludes pipeline blocks and non-trainable-state ops it alone can
#: see; an over-approximate simulator plan only mis-prices, never
#: mis-executes, those segments)
REMAT_IMPURE_TYPES = frozenset({
    OperatorType.INPUT, OperatorType.CACHE, OperatorType.GROUP_BY,
    OperatorType.AGGREGATE, OperatorType.AGGREGATE_SPEC,
})


def remat_segments(ops: Sequence[Op]) -> List[Tuple[List[Op], bool]]:
    """[(segment, pure)] over a topo-ordered op sequence — the remat
    decision units a strategy's plan indexes: plan entry i names the
    i-th single-tensor-boundary segment.  Impure segments (pure=False)
    always run inline regardless of the plan."""
    from ..pcg.segments import split_segments_ops

    segments, _ = split_segments_ops(list(ops))
    return [
        (seg, all(op.op_type not in REMAT_IMPURE_TYPES for op in seg))
        for seg in segments
    ]


def _axis_sizes_of_view(pt, mesh_axes: Dict[str, int]) -> Dict[str, int]:
    out = {}
    if pt.machine_view is None:
        return out
    for axes in pt.machine_view.axes:
        for ax in axes:
            out[ax] = mesh_axes[ax]
    return out


class Simulator:
    """Strategy cost evaluation (replaces simulate_runtime's event loop
    for the SPMD execution model; see module docstring)."""

    def __init__(
        self,
        machine: MachineModel,
        cost_model: Optional[OpCostModel] = None,
        overlap_fraction: float = 0.3,
        optimizer_slots: int = 2,  # adam m+v
        sync_overlap_fraction: Optional[float] = None,
        parameter_sync: str = "allreduce",
        remat: bool = False,
        compute_scale: float = 1.0,
        weight_update_sharding: bool = False,
        wus_axis: str = "data",
        zero_stage: Optional[int] = None,
        placement: Optional[str] = None,
        dcn_bucket_bytes: float = DEFAULT_DCN_BUCKET_BYTES,
    ):
        self.machine = machine
        self.cost_model = cost_model or OpCostModel(machine)
        self.overlap_fraction = overlap_fraction
        # fitted backend calibration (sim/calibrate.py): scales the
        # analytic compute term to measured reality; 1.0 = roofline
        self.compute_scale = compute_scale
        self.optimizer_slots = optimizer_slots
        # executor --remat: checkpointed segments change peak memory
        self.remat = remat
        # gradient-sync overlap with remaining backward compute
        # (reference --search-overlap-backward-update, config.h:130):
        # None -> same credit as other comm
        self.sync_overlap_fraction = (
            sync_overlap_fraction if sync_overlap_fraction is not None
            else overlap_fraction
        )
        # "allreduce" (ring, NCCL-equivalent) | "ps" (parameter server:
        # flat 2*size/BW, reference default_estimate_sync_cost
        # simulator.cc:786-813 + ParameterSyncType::PS optimizer.h:47)
        self.parameter_sync = parameter_sync
        # ZeRO ladder stage (docs/PERF.md "The ZeRO ladder").  This is
        # the simulator's DEFAULT stage; every stage-sensitive method
        # also takes a per-call zero_stage override (keyed into the
        # OpTerms cache) so one simulator can cost all four rungs of
        # the ladder for the searches:
        #   1: grad reduce-scatter, update numel/rep, post-update
        #      weight all-gather, slot memory /rep;
        #   2: + gradient-resident bytes /rep;
        #   3: + master-weight-resident bytes /rep, per-layer weight
        #      all-gathers (fwd + bwd) instead of the post-update one.
        # weight_update_sharding=True is the deprecated alias for
        # stage 1; the bool attribute mirrors `zero_stage >= 1`.
        self.zero_stage = (
            int(zero_stage) if zero_stage is not None
            else (1 if weight_update_sharding else 0)
        )
        self.weight_update_sharding = self.zero_stage >= 1
        # the ONE mesh axis the executor shards the update over
        # (FFConfig.wus_axis); wus_group() resolves each weight's
        # actual sharding group from it
        self.wus_axis = wus_axis
        # multi-slice hierarchy (topology/hierarchy.py): placement is
        # the DEFAULT cross-slice mesh axis; every placement-sensitive
        # method also takes a per-call override (keyed into the OpTerms
        # cache) so one simulator costs every placement for the
        # searches.  Single-slice machines ignore it entirely — the
        # flat costs are bit-identical to the pre-topology model.
        self.placement = placement
        self._slices = max(1, int(getattr(machine, "slices", 1) or 1))
        self._hier = (
            self._slices > 1 and hasattr(machine, "collective_cost")
        )
        # DCN grad-sync bucketing (ROADMAP multi-slice follow-up 1):
        # runtimes coalesce grad all-reduces into ~bucket-sized chunks,
        # so a leaf's DCN latency term is amortized by the fraction of
        # a bucket its DCN-leg bytes fill.  0/None disables (pay the
        # full per-leaf latency, the pre-v4 behavior).  Flat machines
        # never consult it — there is no DCN leg to bucket.
        self.dcn_bucket_bytes = dcn_bucket_bytes
        # (node_key, mesh signature, training) -> OpTerms: per-op
        # contribution terms for the delta/memoized evaluator (the
        # machine and sync mode are fixed per Simulator)
        self._term_cache: Dict[Tuple, OpTerms] = {}
        self.term_hits = 0
        self.term_misses = 0
        # (params, input shape) -> reconstructed member sub-ops for
        # FUSED_PARALLEL costing (rebuilt on every call before)
        self._fused_members: Dict[Tuple, List[Op]] = {}

    # -- comm costs ------------------------------------------------------
    def _collective(self, kind: str, size: float, group_len: int,
                    cross: bool = False, grad_bucket: bool = False):
        """One collective as a topology.CommCost: the flat single-tier
        estimate on ordinary machines (everything ICI), the
        hierarchical / DCN synthesis on a SliceHierarchy when the
        group spans the slice boundary (`cross`).

        `grad_bucket` marks gradient-sync legs: their DCN latency term
        is amortized by the bucket fraction the leaf's DCN-leg bytes
        fill (dcn_bucket_bytes), because real runtimes coalesce grad
        all-reduces into buckets — many small leaves then cost
        latency-sublinear in leaf count while total bytes are
        unchanged.  Activation/resharding collectives are NOT bucketed
        (each is a real standalone collective on the wire)."""
        if group_len <= 1:
            return ZERO_COST
        if self._hier:
            lat_scale = 1.0
            if grad_bucket and cross and self.dcn_bucket_bytes:
                intra, _ = self.machine.split_group(group_len)
                dcn_size = size / intra if intra > 1 else size
                lat_scale = min(1.0, dcn_size / self.dcn_bucket_bytes)
            return self.machine.collective_cost(kind, size, group_len,
                                                cross=cross,
                                                dcn_lat_scale=lat_scale)
        return CommCost(
            ici_time=self._collective_time(kind, size, group_len),
            ici_bytes=ring_bytes(kind, size, group_len),
        )

    def _collective_time(self, kind: str, size: int, group_len: int,
                         over_dcn: bool = False) -> float:
        m = self.machine
        if isinstance(m, TpuPodModel):
            if kind == "allreduce":
                return m.axis_allreduce_time(size, group_len, over_dcn)
            if kind in ("allgather", "reducescatter"):
                return m.axis_allgather_time(size, group_len, over_dcn)
            if kind == "alltoall":
                return m.axis_alltoall_time(size, group_len, over_dcn)
        group = list(range(group_len))
        if kind == "allreduce":
            return m.allreduce_time(size, group)
        if kind in ("allgather", "reducescatter"):
            return m.allgather_time(size, group)
        return m.alltoall_time(size, group)

    # -- placement / tier decisions (topology/hierarchy.py) --------------
    def effective_placement(self, mesh_axes: Optional[Dict[str, int]],
                            placement: Optional[str]) -> Optional[str]:
        """The cross-slice mesh axis one evaluation costs under: the
        per-call override (searches costing placements), else the
        simulator default, else the shared resolve_placement default —
        always validated against the mesh (an axis the slice count
        cannot divide falls back to the default).  None on flat
        machines, so every tier decision degrades to ICI."""
        if not self._hier or not mesh_axes:
            return None
        from ..topology.hierarchy import resolve_placement

        p = placement if placement is not None else self.placement
        if p is not None:
            n = mesh_axes.get(p, 0)
            if n >= self._slices and n % self._slices == 0:
                return p
        return resolve_placement(mesh_axes, self._slices)

    @staticmethod
    def _view_axes(pt) -> frozenset:
        view = getattr(pt, "machine_view", None)
        if view is None:
            return frozenset()
        return frozenset(view.used_axes())

    def _xfer_crosses(self, op: Op, eff_p: Optional[str]) -> bool:
        """Does a parallel op's resharding collective ride the
        cross-slice axis?  The moved degrees are the axes entering or
        leaving between input and output views (best-effort: views are
        assigned on the evaluator's applied graphs; viewless fallback
        stays ICI)."""
        if eff_p is None or not op.inputs or not op.outputs:
            return False
        return eff_p in (
            self._view_axes(op.inputs[0]) ^ self._view_axes(op.outputs[0])
        )

    def _partial_crosses(self, op: Op, eff_p: Optional[str]) -> bool:
        """Does a contraction partial-sum all-reduce span slices?  The
        psum group rides the output's replica-dim axes."""
        if eff_p is None or not op.outputs:
            return False
        view = getattr(op.outputs[0], "machine_view", None)
        if view is None:
            return False
        for dim, axes in zip(op.outputs[0].shape.dims, view.axes):
            if dim.is_replica_dim and eff_p in axes:
                return True
        return False

    def _weight_rep_crosses(self, w, eff_p: Optional[str]) -> bool:
        """Does this weight's gradient-sync replica group include the
        cross-slice axis?  True unless the placement axis SHARDS the
        weight (then its replicas all live inside one slice)."""
        if eff_p is None:
            return False
        view = getattr(w, "machine_view", None)
        if view is not None:
            for dim, axes in zip(w.shape.dims, view.axes):
                if not dim.is_replica_dim and eff_p in axes:
                    return False
        return True

    def xfer_cost(self, op: Op, mesh_axes: Dict[str, int]) -> float:
        """Cost of a parallel op's resharding collective (reference
        estimate_xfer_cost per type, simulator.cc:622-767).  Flat
        (single-tier) estimate — op_terms costs the placement-aware
        form through _xfer_cc."""
        return self._xfer_cc(op, mesh_axes, cross=False).time

    def _xfer_cc(self, op: Op, mesh_axes: Dict[str, int],
                 cross: bool = False):
        """The resharding collective as a per-tier CommCost; `cross`
        routes it over the slice boundary on hierarchy machines."""
        overhead = CommCost(ici_time=_KERNEL_OVERHEAD)
        if not op.is_parallel_op():
            return ZERO_COST
        inp, out = op.inputs[0].shape, op.outputs[0].shape
        shard_bytes = out.shard_bytes()
        t = op.op_type
        if t == OperatorType.REPARTITION:
            # slicing data already on-device under SPMD: near-free when
            # coming from replicated, all-to-all otherwise
            degree = op.params.degree
            if inp.total_degree == 1 or inp.replica_degree >= degree:
                return overhead
            return self._collective("alltoall", shard_bytes, degree, cross)
        if t == OperatorType.COMBINE:
            return self._collective(
                "allgather", inp.shard_bytes() * op.params.degree,
                op.params.degree, cross,
            )
        if t == OperatorType.REPLICATE:
            return self._collective(
                "allgather", shard_bytes, op.params.degree, cross
            )
        if t == OperatorType.REDUCTION:
            return self._collective(
                "allreduce", shard_bytes, op.params.degree, cross
            )
        if t == OperatorType.ALLTOALL:
            return self._collective(
                "alltoall", shard_bytes, op.params.degree, cross
            )
        if t == OperatorType.FUSED_PARALLEL:
            # one boundary, but each fused member still moves its bytes
            # (reference estimate_xfer_cost on FusedParallelOp walks the
            # member ops); shape propagates member to member
            key = (op.params, inp)
            members = self._fused_members.get(key)
            if members is None:
                from ..parallel.parallel_op import PARALLEL_OP_KINDS
                from ..tensor import ParallelTensor

                members = []
                shape = inp
                for kind, params in op.params.ops:
                    sub = PARALLEL_OP_KINDS[kind](params, [ParallelTensor(shape)])
                    members.append(sub)
                    shape = sub.outputs[0].shape
                self._fused_members[key] = members
            total = ZERO_COST
            for sub in members:
                total = total + self._xfer_cc(sub, mesh_axes, cross)
            return total if total.time > _KERNEL_OVERHEAD else overhead
        return overhead

    def partial_sum_cost(self, op: Op, mesh_axes: Dict[str, int]) -> float:
        """An op whose output replica degree exceeds its inputs' implies
        a contraction-dim partial sum -> all-reduce inserted by SPMD."""
        return self._partial_cc(op, mesh_axes, cross=False).time

    def _partial_cc(self, op: Op, mesh_axes: Dict[str, int],
                    cross: bool = False):
        if op.is_parallel_op() or not op.outputs:
            return ZERO_COST
        out_rep = op.outputs[0].shape.replica_degree
        in_rep = max((t.shape.replica_degree for t in op.inputs), default=1)
        if out_rep > in_rep:
            k = out_rep // max(1, in_rep)
            return self._collective(
                "allreduce", op.outputs[0].shape.shard_bytes(), k, cross
            )
        return ZERO_COST

    def sync_time(self, size: int, rep: int) -> float:
        """One weight's gradient sync under the configured
        ParameterSyncType: ring all-reduce, the parameter-server
        estimate 2*size/BW (reference simulator.cc:786-813), or free
        under NONE (reference config.h:55: no sync)."""
        if self.parameter_sync == "none":
            return 0.0
        if self.parameter_sync == "ps":
            bw, lat = self.machine.ps_link()
            return 2.0 * lat + 2.0 * size / bw
        return self._collective_time("allreduce", size, rep)

    def _stage(self, zero_stage: Optional[int]) -> int:
        """Effective ZeRO stage for one call: the per-call override
        (searches costing the ladder), else the simulator default."""
        return self.zero_stage if zero_stage is None else int(zero_stage)

    def wus_group(self, w, mesh_axes: Optional[Dict[str, int]] = None,
                  zero_stage: Optional[int] = None,
                  placement: Optional[str] = None) -> int:
        """The group size this weight's update actually shards over —
        the executor-fidelity mirror of parallel/zero.py.  1 means the
        leaf keeps the replicated update (wus off, a mesh without the
        wus axis, a weight not replicated over it, or no free logical
        dim evenly divisible by it), so it must keep replicated
        cost/memory here too.

        The runtime shards over the SINGLE configured wus mesh axis,
        not the weight's whole replica group, so on a mixed mesh
        ({data: 4, model: 2}) an 8-way-replicated weight shards 4-ways.
        Eligibility mirrors zero.py's rule exactly: the axis must be
        unused by the weight's spec — i.e. by its non-replica dims
        (replication is expressed by omission, so a replica-dim entry
        doesn't block) — and a free logical dim must divide evenly.
        Callers without mesh context (unity's per-op DP stage) fall
        back to the replica degree — exact on pure-dp meshes, and the
        authoritative evaluation always re-scores with mesh_axes.

        `placement` (the effective cross-slice axis): when the wus axis
        itself spans slices with an intra-slice remainder, the executor
        scatters over THAT remainder only (the expanded mesh's reduced
        axis, topology.expand_mesh_axes) — so the group shrinks to
        n / slices and the inter-slice leg rides grad_sync as a DCN
        all-reduce of the scattered shard."""
        if self._stage(zero_stage) < 1 or self.parameter_sync == "none":
            return 1
        if mesh_axes is None:
            n = w.shape.replica_degree
            if n <= 1:
                return 1
        else:
            n = mesh_axes.get(self.wus_axis, 1)
            if (placement == self.wus_axis and self._slices > 1
                    and n > self._slices and n % self._slices == 0):
                n //= self._slices
            if n <= 1:
                return 1
            view = getattr(w, "machine_view", None)
            if view is not None and any(
                self.wus_axis in axes
                for dim, axes in zip(w.shape.dims, view.axes)
                if not dim.is_replica_dim
            ):
                return 1  # axis already shards a logical dim
        if not any(
            not d.is_replica_dim and d.degree == 1
            and d.size > 0 and d.size % n == 0
            for d in w.shape.dims
        ):
            return 1
        return n

    def weight_update_comm(self, size: int, rep: int,
                           zero_stage: Optional[int] = None
                           ) -> Tuple[float, float, float]:
        """One weight's (grad-sync, post-update-all-gather, per-layer
        gather) times under the effective ZeRO stage.

        Replicated update (stage 0): ring all-reduce of the grad
        (sync_time), no gathers.  Stages 1/2: reduce-scatter the grad +
        all-gather the updated weight — the same ring bytes as the
        all-reduce, split around an update that now touches only
        numel/rep elements (stage 2 differs from 1 in MEMORY only: the
        grad buffer stays scattered).  Stage 3: the post-update gather
        disappears — weights stay resident-scattered — and instead the
        step pays TWO per-layer all-gathers (forward use + backward
        re-gather), credited with the double-buffered-prefetch overlap
        (Z3_PREFETCH_OVERLAP), not the generic one.  parameter_sync
        "none" keeps replicas unsynced, which the sharded update cannot
        express — it stays on the replicated path."""
        s, x, gx = self._weight_update_comm_cc(size, rep,
                                               zero_stage=zero_stage)
        return s.time, x.time, gx.time

    def _weight_update_comm_cc(self, size: int, rep: int,
                               zero_stage: Optional[int] = None,
                               cross: bool = False):
        """weight_update_comm as per-tier CommCosts: (grad leg,
        post-update gather, stage-3 per-layer gathers).  `cross` routes
        the group over the slice boundary — the placement axis exactly
        equal to the slice count, where the scattered update's RS/AG
        ride DCN whole (an intra-slice remainder instead shrinks the
        group and keeps these legs on ICI; see wus_group)."""
        stage = self._stage(zero_stage)
        if stage < 1 or self.parameter_sync == "none":
            t = self.sync_time(size, rep)
            sync = CommCost(ici_time=t, ici_bytes=(
                2.0 * size if (t and self.parameter_sync == "ps")
                else ring_bytes("allreduce", size, rep)
            )) if t else ZERO_COST
            return sync, ZERO_COST, ZERO_COST
        if self.parameter_sync == "ps":
            # flat 2*size/BW grad leg rides the ps link (single-tier)
            sync = CommCost(ici_time=self.sync_time(size, rep),
                            ici_bytes=2.0 * size)
        else:
            sync = self._collective("reducescatter", size, rep, cross,
                                    grad_bucket=True)
        gather = self._collective("allgather", size, rep, cross)
        if stage >= 3:
            return sync, ZERO_COST, gather + gather
        return sync, gather, ZERO_COST

    def grad_sync_cost(self, graph: Graph, mesh_axes: Dict[str, int]) -> float:
        """Gradient sync over each weight's replica axes (SPMD's psum in
        backward == reference optimizer ncclAllReduce; PS path
        optimizer.h:47-58)."""
        total = 0.0
        for op in graph.ops:
            for w in op.weights:
                rep = w.shape.replica_degree
                if rep > 1 and w.create_gradients:
                    total += self.sync_time(w.shape.shard_bytes(), rep)
        return total

    # -- per-op contribution terms (delta-sim decomposition) -------------
    def op_terms(self, op: Op, mesh_axes: Dict[str, int],
                 training: bool = True, skip_compute: bool = False,
                 zero_stage: Optional[int] = None,
                 placement: Optional[str] = None) -> OpTerms:
        """All of `op`'s additive contributions to simulate(), cached by
        (node_key, mesh signature, training).  node_key already encodes
        params + ShardConfig + input parallel shapes, so a strategy move
        that leaves an op's config and input shapes unchanged reuses its
        terms across candidates.  skip_compute: the op's compute is
        covered by a measured segment — don't run (or cache-measure) the
        per-op cost model for a term the aggregation will discard.

        On a SliceHierarchy machine, `placement` (per-call override of
        the simulator default) decides which mesh axis spans the DCN
        boundary: collectives whose group rides it cost the
        hierarchical / DCN synthesis, everything else stays on ICI, and
        the ici_/dcn_ tier fields carry the split."""
        # mesh signature preserves INSERTION order (not sorted): views —
        # which wus_group reads — are assigned by assign_axes' axis-
        # declaration-order heuristic, so two orderings of equal-size
        # axes are distinct mesh configurations and must not alias one
        # cache entry (strategy_signature keeps order for the same
        # reason)
        stage = self._stage(zero_stage)
        eff_p = self.effective_placement(mesh_axes, placement)
        # stage only shapes the weight-update terms, so weightless ops
        # are stage-invariant — key them at a single rung so a stage
        # sweep doesn't recompute their compute/xfer terms per stage
        key = (op.node_key(), tuple(mesh_axes.items()), training,
               skip_compute, stage if op.weights else 0, eff_p)
        hit = self._term_cache.get(key)
        if hit is not None:
            self.term_hits += 1
            return hit
        self.term_misses += 1
        compute = xfer = partial = grad_sync = opt_numel = 0.0
        opt_xfer = gather_xfer = 0.0
        fwd_time = recompute_extra = 0.0
        tiers = ZERO_COST  # per-tier time/bytes over every comm term
        mem_weights = mem_master = mem_grad = mem_gather = 0
        mem_opt = mem_residual = mem_transient = 0
        if op.op_type != OperatorType.INPUT:
            if op.is_parallel_op():
                cc = self._xfer_cc(op, mesh_axes,
                                   cross=self._xfer_crosses(op, eff_p))
                xfer = cc.time
                tiers = tiers + cc
            else:
                cc = self._partial_cc(op, mesh_axes,
                                      cross=self._partial_crosses(op, eff_p))
                partial = cc.time
                tiers = tiers + cc
                if training:
                    tiers = tiers + cc  # bwd mirror (simulate_ops's 2x)
                if not skip_compute:
                    cm = self.cost_model.cost(op)
                    fwd_time = cm.forward_time
                    compute = cm.forward_time + (
                        cm.backward_time if training else 0.0
                    )
        for w in op.weights:
            sb = w.shape.shard_bytes()
            mem_weights += sb
            opt_sb = sb
            master_sb = grad_sb = sb
            if w.create_gradients:
                numel = sb / max(
                    1, np.dtype(w.shape.dtype.np_dtype).itemsize
                )
                rep = w.shape.replica_degree
                g = self.wus_group(w, mesh_axes, zero_stage=stage,
                                   placement=eff_p)
                if g > 1:
                    # whole-axis crossing: the wus axis IS the slice dim
                    # (no intra remainder), so the scattered update's
                    # RS/AG ride DCN; with a remainder, wus_group shrank
                    # g to it and these legs stay on ICI
                    cross_whole = (
                        eff_p is not None and eff_p == self.wus_axis
                        and mesh_axes.get(self.wus_axis, 1) == self._slices
                    )
                    s_cc, x_cc, gx_cc = self._weight_update_comm_cc(
                        sb, g, zero_stage=stage, cross=cross_whole
                    )
                    grad_sync += s_cc.time
                    wcc = s_cc + x_cc + gx_cc
                    if (rep > g and rep % g == 0
                            and self.parameter_sync == "allreduce"):
                        # tracked replication beyond the (intra) wus
                        # group still all-reduces on the scattered
                        # shard — over DCN when the slice factor is in
                        # that remainder (the hierarchical reduction's
                        # inter-slice leg)
                        rem_cc = self._collective(
                            "allreduce", sb // g, rep // g,
                            cross=(not cross_whole
                                   and self._weight_rep_crosses(w, eff_p)),
                            grad_bucket=True,
                        )
                        grad_sync += rem_cc.time
                        wcc = wcc + rem_cc
                    opt_xfer += x_cc.time
                    gather_xfer += gx_cc.time
                    if training and gx_cc.time:
                        # ZeRO-3 x remat: backward recompute re-emits
                        # the per-layer gather INSIDE the checkpointed
                        # segment (executor keeps z3_cache=None under
                        # remat), where the double-buffered prefetch
                        # cannot run — one of the two gathers loses its
                        # credit.  The lost credit rides `recompute`
                        # (charged at full exposure only when the op's
                        # segment is ON), so remat-off plans keep
                        # today's gather_xfer pricing exactly.
                        recompute_extra += (
                            gx_cc.time / 2.0
                        ) * Z3_PREFETCH_OVERLAP
                    if training:
                        tiers = tiers + wcc
                    # the update runs on the 1/g shard; slots live
                    # there permanently
                    numel /= g
                    opt_sb = sb // g
                    if stage >= 2:
                        # ZeRO-2: the grad buffer stays reduce-scattered
                        # through the update — 1/g resident per device
                        grad_sb = sb // g
                    if stage >= 3:
                        # ZeRO-3/FSDP: master lives scattered; the
                        # gathered compute copy is transient (the
                        # double-buffer window rides mem_gather)
                        master_sb = sb // g
                        mem_gather += sb
                elif rep > 1:
                    # replicated update (stage 0, or this leaf falls
                    # back per parallel/zero.py): hierarchical
                    # all-reduce when the replica group spans slices
                    if self.parameter_sync == "allreduce":
                        rcc = self._collective(
                            "allreduce", sb, rep,
                            cross=self._weight_rep_crosses(w, eff_p),
                            grad_bucket=True,
                        )
                    else:
                        t = self.sync_time(sb, rep)
                        rcc = CommCost(
                            ici_time=t, ici_bytes=2.0 * sb
                        ) if t else ZERO_COST
                    grad_sync += rcc.time
                    if training:
                        tiers = tiers + rcc
                opt_numel += numel
            mem_opt += opt_sb
            mem_master += master_sb
            mem_grad += grad_sb
        for t in op.outputs:
            b = t.shape.shard_bytes()
            if op.op_type in self._FUSED_ACT_TYPES:
                mem_transient = max(mem_transient, b)
            else:
                mem_residual += b
        # searched remat (docs/PERF.md): what this op saves per device
        # when its segment is OFF, and what re-running its forward in
        # backward costs when it is ON.  Parallel ops re-run their
        # resharding collective; compute ops re-run forward plus the
        # fwd partial-sum psum; measured (skip_compute) ops contribute
        # no recompute estimate — their fwd split is unknown.
        recompute = 0.0
        if training and op.op_type != OperatorType.INPUT:
            recompute = (
                xfer if op.is_parallel_op()
                else fwd_time + partial
            ) + recompute_extra
        terms = OpTerms(
            compute=compute, xfer=xfer, partial=partial,
            grad_sync=grad_sync, opt_numel=opt_numel, opt_xfer=opt_xfer,
            gather_xfer=gather_xfer,
            ici_xfer=tiers.ici_time, dcn_xfer=tiers.dcn_time,
            ici_bytes=tiers.ici_bytes, dcn_bytes=tiers.dcn_bytes,
            mem_weights=mem_weights, mem_master=mem_master,
            mem_grad=mem_grad, mem_gather=mem_gather, mem_opt=mem_opt,
            mem_residual=mem_residual, mem_transient=mem_transient,
            mem_activation=mem_residual, recompute=recompute,
        )
        self._term_cache[key] = terms
        return terms

    def memory_from_terms(self, ops: Sequence[Op], mesh_axes: Dict[str, int],
                          training: bool = True,
                          zero_stage: Optional[int] = None,
                          placement: Optional[str] = None) -> int:
        """per_device_memory re-aggregated from cached OpTerms — exact
        for the training non-remat accounting (weights + residual sum +
        transient max; all integer bytes, so order-independent).  The
        remat and inference liveness models need whole-graph structure
        and keep using per_device_memory().

        Training weight accounting follows the ZeRO ladder: master
        resident (mem_master: /g at stage 3) + gradient buffer
        (mem_grad: /g at stage 2+) + slot bytes (mem_opt: /g at 1+) +
        the stage-3 double-buffered gather window (2x the largest op's
        gathered weight copies).  At stages 0/1 this is bit-identical
        to the pre-ladder weights*2 + slots*opt formula."""
        compute_copy = master = grads = opt = residuals = transient = 0
        gather_peak = 0
        for op in ops:
            terms = self.op_terms(op, mesh_axes, training,
                                  zero_stage=zero_stage,
                                  placement=placement)
            compute_copy += terms.mem_weights
            master += terms.mem_master
            grads += terms.mem_grad
            opt += terms.mem_opt
            residuals += terms.mem_residual
            transient = max(transient, terms.mem_transient)
            gather_peak = max(gather_peak, terms.mem_gather)
        if training:
            weights = (master + grads + self.optimizer_slots * opt
                       + 2 * gather_peak)
        else:
            weights = compute_copy
        return int(weights + residuals + transient)

    # -- searched rematerialization (docs/PERF.md) -----------------------
    def remat_layout(self, ops: Sequence[Op],
                     plan: Optional[Sequence[int]],
                     op_scale=None) -> Tuple[set, float, float]:
        """(on_guids, residual_bytes, worst_internal) for a per-segment
        remat plan over a topo-ordered op sequence.

          * on_guids — guids of ops inside ON (and pure) segments, whose
            `recompute` term the aggregation charges;
          * residual_bytes — activations that persist to backward under
            the plan: every segment-boundary tensor (the checkpoint
            saves — live as later segments' inputs either way) plus the
            internals of OFF / impure segments;
          * worst_internal — the largest ON segment's internal bytes,
            alive only while that segment's backward recomputes.

        plan=None means every pure segment is ON (the legacy --remat
        shape); an empty plan reproduces the dense accounting exactly
        (residual_bytes == the sum of mem_activation terms)."""
        from ..pcg.segments import split_segments_ops

        ops = list(ops)
        segments, boundaries = split_segments_ops(ops)
        boundary_guids = {g for g in boundaries if g is not None}
        sel = None if plan is None else {int(i) for i in plan}
        sc = op_scale or (lambda op: 1.0)
        on_guids: set = set()
        residual = 0.0
        worst = 0.0
        for i, seg in enumerate(segments):
            pure = all(op.op_type not in REMAT_IMPURE_TYPES for op in seg)
            on = pure and (sel is None or i in sel)
            internal = 0.0
            for op in seg:
                if op.op_type in self._FUSED_ACT_TYPES:
                    continue  # transient workspace, never a residual
                for t in op.outputs:
                    b = t.shape.shard_bytes() * sc(op)
                    if t.guid in boundary_guids:
                        residual += b
                    else:
                        internal += b
            if on:
                worst = max(worst, internal)
                on_guids.update(op.guid for op in seg)
            else:
                residual += internal
        return on_guids, residual, worst

    def remat_memory_from_terms(
        self, ops: Sequence[Op], mesh_axes: Dict[str, int],
        plan: Optional[Sequence[int]], training: bool = True,
        zero_stage: Optional[int] = None,
        placement: Optional[str] = None,
    ) -> int:
        """per_device_memory under a per-segment remat plan, aggregated
        from cached OpTerms + one O(n) segment sweep over the op
        sequence — usable on the evaluator's DELTA path (no Graph
        needed), unlike the legacy whole-graph _remat_peak.  Weight /
        optimizer residency is identical to memory_from_terms (the
        ZeRO ladder accounting); only the activation term changes.  An
        all-OFF plan is bit-identical to memory_from_terms."""
        compute_copy = master = grads = opt = transient = 0
        gather_peak = 0
        for op in ops:
            terms = self.op_terms(op, mesh_axes, training,
                                  zero_stage=zero_stage,
                                  placement=placement)
            compute_copy += terms.mem_weights
            master += terms.mem_master
            grads += terms.mem_grad
            opt += terms.mem_opt
            transient = max(transient, terms.mem_transient)
            gather_peak = max(gather_peak, terms.mem_gather)
        _, residual, worst = self.remat_layout(ops, plan)
        if training:
            weights = (master + grads + self.optimizer_slots * opt
                       + 2 * gather_peak)
        else:
            weights = compute_copy
        return int(weights + residual + worst + transient)

    # -- memory ----------------------------------------------------------

    #: outputs XLA recomputes inside fusions rather than materializing
    #: as backward residuals — they cost transient workspace, not
    #: step-long liveness
    _FUSED_ACT_TYPES = frozenset({
        OperatorType.ELEMENT_UNARY, OperatorType.ELEMENT_BINARY,
        OperatorType.CAST, OperatorType.DROPOUT,
    })

    def per_device_memory(self, graph: Graph, training: bool = True,
                          op_scale=None, remat: Optional[bool] = None,
                          mesh_axes: Optional[Dict[str, int]] = None,
                          zero_stage: Optional[int] = None,
                          placement: Optional[str] = None) -> int:
        """Peak per-device bytes: weights (+grads+optimizer slots when
        training) plus LIVE activations, not the sum of every tensor
        ever produced (the r02 model summed all of them, so
        memory_search optimized a systematically inflated objective).

          * training, no remat: backward residuals = outputs of
            non-fused ops persist to their backward; fused elementwise
            outputs only cost transient workspace (max single one);
          * training, remat: only single-tensor segment boundaries
            persist (jax.checkpoint semantics, executor._build_remat_plan)
            plus the largest segment's internals for recomputation;
          * inference: a liveness scan — a tensor dies after its last
            consumer.

        op_scale(op) -> float scales an op's contribution (pipeline
        strategies pass 1/num_stages for block ops — each device holds
        only its stage's weights/activations)."""
        remat = self.remat if remat is None else remat
        stage = self._stage(zero_stage)
        eff_p = self.effective_placement(mesh_axes, placement)
        scale = (lambda op: op_scale(op)) if op_scale is not None \
            else (lambda op: 1.0)
        weights = sum(
            w.shape.shard_bytes() * scale(op)
            for op in graph.ops for w in op.weights
        )
        if training:
            if stage >= 1 and self.parameter_sync != "none":
                # ZeRO ladder: slots of grad-bearing replicated weights
                # live on their 1/group shard (stage 1+); the gradient
                # buffer joins them at stage 2+ and the master weights
                # at stage 3 (plus the 2-layer gathered-copy window);
                # unshardable leaves fall back whole at every rung
                master = grads = opt = 0.0
                gather_peak = 0.0
                for op in graph.ops:
                    op_gather = 0.0
                    for w in op.weights:
                        sb = w.shape.shard_bytes()
                        sc = scale(op)
                        g = (self.wus_group(w, mesh_axes, zero_stage=stage,
                                            placement=eff_p)
                             if w.create_gradients else 1)
                        opt += (sb // g) * sc
                        grads += (sb // g if stage >= 2 else sb) * sc
                        if g > 1 and stage >= 3:
                            master += (sb // g) * sc
                            op_gather += sb * sc
                        else:
                            master += sb * sc
                    gather_peak = max(gather_peak, op_gather)
                weights = (master + grads + self.optimizer_slots * opt
                           + 2 * gather_peak)
            else:
                # master copy + grads + optimizer slots
                weights *= (2 + self.optimizer_slots)

        if not training:
            acts = self._liveness_peak(graph, scale)
        elif remat:
            acts = self._remat_peak(graph, scale)
        else:
            residuals = 0.0
            transient = 0.0
            for op in graph.ops:
                for t in op.outputs:
                    b = t.shape.shard_bytes() * scale(op)
                    if op.op_type in self._FUSED_ACT_TYPES:
                        transient = max(transient, b)
                    else:
                        residuals += b
            acts = residuals + transient
        return int(weights + acts)

    def _liveness_peak(self, graph: Graph, scale) -> float:
        from ..pcg.segments import last_use_positions

        topo = graph.topo_order()
        last_use = last_use_positions(topo)
        bytes_of: Dict[int, float] = {
            t.guid: t.shape.shard_bytes() * scale(op)
            for op in topo for t in op.outputs
        }
        live = peak = 0.0
        for i, op in enumerate(topo):
            for t in op.outputs:
                live += bytes_of[t.guid]
            peak = max(peak, live)
            for t in op.inputs:
                if last_use.get(t.guid) == i:
                    live -= bytes_of.get(t.guid, 0.0)
        return peak

    def _remat_peak(self, graph: Graph, scale) -> float:
        from ..pcg.segments import split_segments

        impure = {OperatorType.INPUT, OperatorType.CACHE,
                  OperatorType.GROUP_BY, OperatorType.AGGREGATE,
                  OperatorType.AGGREGATE_SPEC}
        segments, boundaries = split_segments(graph)
        boundary_guids = {g for g in boundaries if g is not None}
        bytes_of = {
            t.guid: t.shape.shard_bytes() * scale(op)
            for op in graph.ops for t in op.outputs
        }
        acts = sum(bytes_of[g] for g in boundary_guids)
        worst_internal = 0.0
        for seg in segments:
            pure = all(op.op_type not in impure for op in seg)
            internal = sum(
                bytes_of[t.guid]
                for op in seg for t in op.outputs
                if t.guid not in boundary_guids
                and op.op_type not in self._FUSED_ACT_TYPES
            )
            if pure:
                # recomputed in backward: alive only while this
                # segment's backward runs
                worst_internal = max(worst_internal, internal)
            else:
                acts += internal  # runs inline, residuals persist
        return acts + worst_internal

    def optimizer_update_cost(self, graph: Graph,
                              mesh_axes: Optional[Dict[str, int]] = None,
                              zero_stage: Optional[int] = None,
                              placement: Optional[str] = None) -> float:
        """Weight-update pass: read master weight + grad, write weight,
        touch each optimizer slot — pure HBM traffic in f32 (master
        precision), one fused kernel under jit.  At ZeRO stage >= 1 the
        pass touches only each replicated weight's 1/group shard
        (arXiv:2004.13336); stages 2/3 change residency, not the pass."""
        numel = 0.0
        eff_p = self.effective_placement(mesh_axes, placement)
        for op in graph.ops:
            for w in op.weights:
                if w.create_gradients:
                    sb = w.shape.shard_bytes()
                    n = sb / max(1, np.dtype(w.shape.dtype.np_dtype).itemsize)
                    numel += n / self.wus_group(w, mesh_axes,
                                                zero_stage=zero_stage,
                                                placement=eff_p)
        bytes_moved = numel * 4.0 * (3 + self.optimizer_slots)
        return bytes_moved / self.machine.device().hbm_bandwidth

    # -- top level -------------------------------------------------------
    def simulate(
        self,
        graph: Graph,
        mesh_axes: Dict[str, int],
        training: bool = True,
        segment_costs: Optional[Sequence[Tuple[Sequence[int], float]]] = None,
        zero_stage: Optional[int] = None,
        placement: Optional[str] = None,
        remat_plan: Optional[Sequence[int]] = None,
    ) -> SimResult:
        """segment_costs: [(member op guids, fwd+bwd seconds)] from
        profiler.measure_segment_costs — ops inside a measured region
        take the measurement (fused-granularity calibration); everything
        else stays analytic.

        remat_plan: a strategy's per-segment remat plan (list of ON
        segment indices; docs/PERF.md "Searched rematerialization") —
        charges each ON segment's recompute seconds and prices memory
        with the plan-aware accounting.  None keeps the legacy
        behavior: the `remat` bool changes memory only (_remat_peak),
        never time."""
        measured_ops: Dict[int, float] = {}  # op guid -> its region's cost
        seg_cost_total = 0.0
        if segment_costs:
            for guids, c in segment_costs:
                seg_cost_total += c
                for g in guids:
                    measured_ops[g] = c
        topo = graph.topo_order()
        if training and remat_plan is not None:
            memory_fn = lambda: self.remat_memory_from_terms(  # noqa: E731
                topo, mesh_axes, remat_plan, training,
                zero_stage=zero_stage, placement=placement,
            )
        elif training and not self.remat:
            memory_fn = lambda: self.memory_from_terms(  # noqa: E731
                topo, mesh_axes, training, zero_stage=zero_stage,
                placement=placement,
            )
        else:
            memory_fn = lambda: self.per_device_memory(  # noqa: E731
                graph, training, mesh_axes=mesh_axes, zero_stage=zero_stage,
                placement=placement,
            )
        return self.simulate_ops(
            topo, mesh_axes, training=training, measured_ops=measured_ops,
            seg_cost_total=seg_cost_total, memory_fn=memory_fn,
            zero_stage=zero_stage, placement=placement,
            remat_plan=remat_plan, repeats=graph.repeats(),
        )

    def simulate_ops(
        self,
        ops: Sequence[Op],
        mesh_axes: Dict[str, int],
        training: bool = True,
        measured_ops: Optional[Dict[int, float]] = None,
        seg_cost_total: float = 0.0,
        memory_fn: Optional[Callable[[], int]] = None,
        zero_stage: Optional[int] = None,
        placement: Optional[str] = None,
        remat_plan: Optional[Sequence[int]] = None,
        repeats: Optional[Dict[str, int]] = None,
    ) -> SimResult:
        """Aggregate cached per-op terms over `ops` (a topo-ordered op
        sequence).  `repeats` ({op name: times}, `Graph.repeats()`)
        prices an op inside a repeated region at that many times its
        compute and its partial-sum traffic; its weights, and so its
        gradient sync and update, exist once.  The ONE aggregation path shared by full and delta
        evaluations: the invariant delta_eval(state) == full_eval(state)
        holds bit-for-bit because both sum identical cached OpTerms in
        identical order.  A remat_plan (docs/PERF.md "Searched
        rematerialization") adds each ON segment's `recompute` terms to
        the analytic compute — the segment sweep is a deterministic
        function of the op sequence, so the invariant extends across
        remat flips."""
        measured_ops = measured_ops or {}
        compute = seg_cost_total if training else seg_cost_total / 3.0
        analytic_compute = 0.0  # compute_scale applies ONLY here —
        # measured segment costs are already real backend seconds
        comm = 0.0
        sync = 0.0
        opt_numel = 0.0
        opt_xfer = 0.0
        gather_xfer = 0.0
        ici_time = dcn_time = ici_bytes = dcn_bytes = 0.0
        recompute_s = 0.0
        activation_bytes = 0.0
        on_guids = None
        if training and remat_plan is not None:
            on_guids, activation_bytes, _ = self.remat_layout(
                ops, remat_plan
            )
        breakdown: Dict[str, float] = {}
        for op in ops:
            if op.op_type == OperatorType.INPUT:
                if training and on_guids is None:
                    # keep the dense telemetry consistent with the
                    # memory accounting (and the plan-aware sweep),
                    # which both count input residuals
                    activation_bytes += sum(
                        t.shape.shard_bytes() for t in op.outputs
                    )
                continue
            terms = self.op_terms(op, mesh_axes, training,
                                  skip_compute=op.guid in measured_ops,
                                  zero_stage=zero_stage,
                                  placement=placement)
            if on_guids is None:
                if training:
                    activation_bytes += terms.mem_activation
            elif op.guid in on_guids:
                recompute_s += terms.recompute
                analytic_compute += terms.recompute
            ici_time += terms.ici_xfer
            dcn_time += terms.dcn_xfer
            ici_bytes += terms.ici_bytes
            dcn_bytes += terms.dcn_bytes
            if training:
                sync += terms.grad_sync
                opt_numel += terms.opt_numel
                opt_xfer += terms.opt_xfer
                gather_xfer += terms.gather_xfer
            if op.is_parallel_op():
                comm += terms.xfer
                breakdown[op.name] = terms.xfer
                continue
            times = repeats.get(op.name, 1) if repeats else 1
            ps = terms.partial * times
            if training and ps:
                ps *= 2.0  # fwd psum + bwd mirrored all-gather/psum
            comm += ps
            if op.guid in measured_ops:
                breakdown[op.name] = ps
                continue
            analytic_compute += terms.compute * times
            breakdown[op.name] = terms.compute * times + ps
        if training:
            # weight-update pass (optimizer_update_cost, from cached
            # per-op numel terms)
            bytes_moved = opt_numel * 4.0 * (3 + self.optimizer_slots)
            analytic_compute += bytes_moved / self.machine.device().hbm_bandwidth
        # XLA overlaps collectives with independent compute; gradient
        # sync gets its own credit when backward/update overlap is
        # modeled (--search-overlap-backward-update).  The sharded
        # update's weight all-gather (opt_xfer, stages 1/2) overlaps
        # the NEXT step's forward the way other collectives overlap
        # compute, so it takes the standard credit, not the
        # backward-sync one.  The ZeRO-3 per-layer gathers
        # (gather_xfer) take the EXPLICIT double-buffered-prefetch
        # credit instead — they sit on layer-boundary critical paths
        # and hide worse than generic resharding.
        effective_comm = (
            comm * (1.0 - self.overlap_fraction)
            + sync * (1.0 - self.sync_overlap_fraction)
            + opt_xfer * (1.0 - self.overlap_fraction)
            + gather_xfer * (1.0 - Z3_PREFETCH_OVERLAP)
        )
        compute = compute + analytic_compute * self.compute_scale
        total = compute + effective_comm
        res = SimResult(
            total_time=total,
            compute_time=compute,
            comm_time=comm,
            sync_time=sync,
            breakdown=breakdown,
            memory_fn=memory_fn,
        )
        # uncredited per-tier split of every comm term this aggregation
        # charged — the comm/{ici,dcn}_* telemetry + fidelity payload
        res.comm_tiers = {
            "ici_time": ici_time, "dcn_time": dcn_time,
            "ici_bytes": ici_bytes, "dcn_bytes": dcn_bytes,
        }
        # searched-remat telemetry: plan-aware saved activations + the
        # recompute seconds charged (as-scaled, matching total_time)
        res.activation_bytes = activation_bytes
        res.recompute_s = recompute_s * self.compute_scale
        return res
