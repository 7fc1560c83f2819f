"""Wall seconds of `FFModel.compile` less the strategy search: graph
passes, `init_weights` (which jit-executes, so its compile lands here)
and staging the step functions (program_span)."""


def read(ctx, metric):
    total = ctx.spans.get("ffmodel_compile")
    if total is None:
        return None
    return total - 1e-3 * (ctx.counters.get("search_ms") or 0.0)
