"""Device milliseconds per sweep call in the analytic stage: the ops under
the ``ufa_analytic`` scope of the fused pipeline programs (``run_chunks``),
averaged over the cell's chips."""

from harness import spans


def read(ctx):
    return spans.scope_ms(ctx.trace, spans.pipeline_op_names(ctx.job),
                          r"run_chunks", "ufa_analytic",
                          "sweep.call", kernels=False)
