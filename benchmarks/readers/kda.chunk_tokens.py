"""`kda.chunk_tokens.train`: positions a chunk of the delta rule's core
holds in the step the program built, 0 where an op took the scan a
position: the `kda_chunk_tokens` arg of the program's `build_step_fns`
span (the least over the graph's `KimiDeltaAttention` ops:
`FFModel._attention_core_counts`) (program_counter).  A fall back to the
scan is then seen in the ledger and not only in the rate.  None where
the program emits no such arg (no such op, or a tree from before it)."""


def read(ctx, metric):
    try:
        from flexflow_tpu.obs.trace import spans
    except ImportError:
        return None
    found = [r.args["kda_chunk_tokens"] for r in spans()
             if r.name == "build_step_fns" and "kda_chunk_tokens" in r.args]
    return float(found[-1]) if found else None
