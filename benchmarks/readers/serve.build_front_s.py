"""Wall seconds of `build_front` plus the first requests, which carry
the lazy compiles of the prefill and decode steps (program_span)."""


def read(ctx, metric):
    if "build_front" not in ctx.spans:
        return None
    return ctx.spans["build_front"] + ctx.spans.get("first_requests", 0.0)
