"""Device milliseconds per sweep call in the dependency stage: the ops under
the ``ufa_dependency`` scope of the fused pipeline programs
(``run_chunks``), its propagation kernel and the per-scenario gathers
included, averaged over the cell's chips."""

from harness import spans


def read(ctx):
    return spans.scope_ms(ctx.trace, spans.pipeline_op_names(ctx.job),
                          r"run_chunks", "ufa_dependency",
                          "sweep.call", kernels=True)
