"""`LoopPasses`: every pass's output of a repeated region, stacked.

A `LoopRegion` (pcg/graph.py, `FFModel.repeat`) runs its ops `times`
times; what leaves it is the LAST pass's output.  A graph that also
needs what each pass made (an exit gate read after every pass) asks
with this op: input the region's carried output `[...]`, output
`[times, ...]`.  It has no forward of its own: the executor runs the
region as one `lax.scan` and the scan's stacked outputs ARE this op's
output (`GraphExecutor._run_loop_region`).
"""
from __future__ import annotations

import dataclasses

from ..fftype import OperatorType
from ..tensor import ParallelDim, ParallelTensorShape
from .op import Op


@dataclasses.dataclass(frozen=True)
class LoopPassesParams:
    times: int
    region: str


class LoopPasses(Op):
    op_type = OperatorType.LOOP_PASSES

    def infer_output_shapes(self, input_shapes):
        (ishape,) = input_shapes
        return [ParallelTensorShape(
            (ParallelDim(self.params.times),) + tuple(ishape.dims),
            ishape.dtype)]

    def forward(self, inputs, weights, *, training=False, rng=None):
        raise RuntimeError(
            f"{self.name}: the executor fills a LoopPasses output from "
            f"the scan of region {self.params.region!r}; it is never "
            "called")
