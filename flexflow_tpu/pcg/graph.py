"""Parallel Computation Graph structure.

Reference: src/runtime/graph.cc (2753 LoC) — Graph over `Node`s with
in/out edge maps, split algorithms for the DP search, and Legion-buffer
strategy serialization (graph.cc:2164-2400).  Fresh design: ops hold
their producer links via ParallelTensor.owner_op, so the graph is the op
list + derived edge maps; strategy serialization is JSON
(flexflow_tpu/strategy.py) instead of a Legion serializer.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from ..fftype import OperatorType
from ..ops.op import Op


@dataclasses.dataclass(frozen=True)
class LoopRegion:
    """A contiguous run of ops that executes `times` times over ONE
    copy of its weights (`FFModel.repeat`).  Pass t + 1 starts from
    pass t's `carry_out`, which takes the place of `carry_in`: the one
    tensor the run consumes from outside.  After the last pass
    `carry_out` holds the last pass's value; `passes_op` (a
    `LoopPasses`, or "") holds every pass's, stacked `[times, ...]`.
    Ops and tensors are named, not held: a graph rebuilt under a
    strategy (`apply_strategy`) keeps its regions."""

    name: str
    times: int
    op_names: Tuple[str, ...]
    carry_in: str   # tensor names (`<op>.out<i>`)
    carry_out: str
    passes_op: str = ""


class Graph:
    def __init__(self, ops: Optional[Sequence[Op]] = None,
                 regions: Sequence[LoopRegion] = ()):
        self.ops: List[Op] = list(ops) if ops else []
        self.regions: List[LoopRegion] = list(regions)
        #: the op whose output is the graph's, where the graph has
        #: another sink besides (a gate that is read, not consumed);
        #: None: the last sink (`FFModel.set_output`)
        self.output_name: Optional[str] = None

    def repeats(self) -> Dict[str, int]:
        """{op name: times its region runs it}, for the ops inside a
        `LoopRegion`; every other op runs once.  What a walk over ops
        that sums a cost (FLOPs, simulated time) multiplies by."""
        return {name: r.times for r in self.regions for name in r.op_names}

    def add_op(self, op: Op):
        self.ops.append(op)
        return op

    # -- structure -------------------------------------------------------
    def producers(self, op: Op) -> List[Op]:
        out = []
        for t in op.inputs:
            if t.owner_op is not None and t.owner_op is not op:
                out.append(t.owner_op)
        return out

    def consumers(self, op: Op) -> List[Op]:
        out = []
        for other in self.ops:
            if other is op:
                continue
            for t in other.inputs:
                if t.owner_op is op:
                    out.append(other)
                    break
        return out

    def in_edges(self) -> Dict[Op, List[Op]]:
        return {op: self.producers(op) for op in self.ops}

    def topo_order(self) -> List[Op]:
        indeg: Dict[int, int] = {}
        by_guid = {op.guid: op for op in self.ops}
        edges = collections.defaultdict(list)  # producer guid -> consumer guids
        for op in self.ops:
            preds = {p.guid for p in self.producers(op) if p.guid in by_guid}
            indeg[op.guid] = len(preds)
            for p in preds:
                edges[p].append(op.guid)
        # stable: seed queue in insertion order
        queue = [op.guid for op in self.ops if indeg[op.guid] == 0]
        order: List[Op] = []
        qi = 0
        while qi < len(queue):
            g = queue[qi]
            qi += 1
            order.append(by_guid[g])
            for c in edges[g]:
                indeg[c] -= 1
                if indeg[c] == 0:
                    queue.append(c)
        if len(order) != len(self.ops):
            raise RuntimeError("PCG has a cycle")
        return order

    def source_ops(self) -> List[Op]:
        return [op for op in self.ops if op.op_type == OperatorType.INPUT]

    def sink_op(self) -> Op:
        if self.output_name is not None:
            (op,) = [op for op in self.ops if op.name == self.output_name]
            return op
        consumed: Set[int] = set()
        for op in self.ops:
            for t in op.inputs:
                consumed.add(t.guid)
        sinks = [
            op
            for op in self.ops
            if op.op_type != OperatorType.INPUT
            and not any(t.guid in consumed for t in op.outputs)
        ]
        if not sinks:
            raise RuntimeError("no sink op")
        return sinks[-1]

    def compute_ops(self) -> List[Op]:
        return [op for op in self.ops if op.op_type != OperatorType.INPUT]

    # -- hashing (search cache key; reference dp_state_hash graph.h:149) --
    def hash_key(self) -> Tuple:
        return tuple(op.node_key() for op in self.topo_order())

    # -- dot export (reference --compgraph/--taskgraph, utils/dot) --------
    def export_dot(self, path: str, include_costs: bool = False, cost_fn=None):
        lines = ["digraph PCG {"]
        for op in self.ops:
            label = f"{op.name}\\n{op.op_type.value}"
            for t in op.outputs:
                label += f"\\n{t.shape}"
            if include_costs and cost_fn is not None:
                label += f"\\ncost={cost_fn(op):.3g}"
            shape = "ellipse" if op.is_parallel_op() else "box"
            lines.append(f'  n{op.guid} [label="{label}", shape={shape}];')
        for op in self.ops:
            for t in op.inputs:
                if t.owner_op is not None:
                    lines.append(f"  n{t.owner_op.guid} -> n{op.guid};")
        lines.append("}")
        with open(path, "w") as f:
            f.write("\n".join(lines))
