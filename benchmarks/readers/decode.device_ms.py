"""`decode.device_ms.latency` / `.capacity`: device busy time of one
decode dispatch, ms: the mean over the traced dispatches of the paged
decode step (`decoding.build_paged_decode_step`, program ``jit_step``)
of the time an operation ran inside it (device_trace)."""
from benchmarks.reduce_trace import mean_dispatch_busy_ms

PROGRAM = "jit_step"


def read(ctx, metric):
    return mean_dispatch_busy_ms(ctx.trace_summary, PROGRAM)
