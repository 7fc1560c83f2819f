"""Wall seconds of the first `train_step` (it carries the step's XLA
compile, or its load from the persistent cache), from the benchmark's
own span (program_span)."""


def read(ctx, metric):
    return ctx.spans.get("first_step")
