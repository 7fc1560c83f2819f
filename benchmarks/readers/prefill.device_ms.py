"""`prefill.device_ms.latency` / `.capacity`: device busy time of one
chunked-prefill dispatch, ms (`decoding.build_paged_prefill_step`,
program ``jit_prefill``: `prefill_chunk` prompt tokens for every slot)
(device_trace)."""
from benchmarks.reduce_trace import mean_dispatch_busy_ms

PROGRAM = "jit_prefill"


def read(ctx, metric):
    return mean_dispatch_busy_ms(ctx.trace_summary, PROGRAM)
