"""`loop.weight_passes.capacity`: how many times a decode dispatch reads
the weights of the graph's repeated regions: the `loop_steps` arg of
`sched.decode.dispatch` (the step program's passes times the regions'),
mean over the traced stretch's decode dispatches (program_counter).  A
program whose spans carry no such arg (no region: the parent of PR 41)
leaves the metric out."""
from benchmarks.decode_dispatch import dispatch_args


def read(ctx, metric):
    got = dispatch_args(ctx, "loop_steps")
    return None if got is None else got["loop_steps"]
