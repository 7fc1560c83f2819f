"""`compile.init_weights_inner_s`: seconds in the `init_weights` span of
the top-level `FFModel.compile` (it jit-executes, so its XLA compile is
in it), from inside the program; `compile.init_weights_s` times the
whole of `compile()` from outside (program_span)."""
from benchmarks import host_spans as hs


def read(ctx, metric):
    return hs.compile_child_seconds(ctx, "init_weights")
