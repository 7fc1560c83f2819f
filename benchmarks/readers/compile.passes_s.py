"""`compile.passes_s`: seconds in the graph passes of the top-level
`FFModel.compile` (the `compile.passes` span: rewrite replay, fusion,
strategy application, views, mesh, executor; everything between the
strategy and the weights), not those of a decode twin, whose `compile`
sits under `serve.build_twin` (program_span)."""
from benchmarks import host_spans as hs


def read(ctx, metric):
    return hs.compile_child_seconds(ctx, "compile.passes")
