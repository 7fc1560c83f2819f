"""`kda.kernel_ops.train`: how many of the graph's delta-rule ops
(`KimiDeltaAttention`, stateless `GatedDeltaNet`) lowered to the Pallas
kernels (`pick_recurrence` = "chunked_kernel":
`ops/pallas/chunked_delta_rule.py`) in the step the program built: the
`kda_kernel_ops` arg of the program's `build_step_fns` span
(`FFModel._attention_core_counts`) (program_counter).  A fall back to
the jax.numpy rule is then seen in the ledger and not only in the rate.
None where the program emits no such arg (no such op, or a tree from
before it)."""


def read(ctx, metric):
    try:
        from flexflow_tpu.obs.trace import spans
    except ImportError:
        return None
    found = [r.args["kda_kernel_ops"] for r in spans()
             if r.name == "build_step_fns" and "kda_kernel_ops" in r.args]
    return float(found[-1]) if found else None
