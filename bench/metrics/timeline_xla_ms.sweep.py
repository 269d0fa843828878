"""Device milliseconds per sweep call in the timeline series: the ops under
the ``ufa_timeline`` scope of the fused pipeline programs (``run_chunks``)
outside the reducer kernel (``reduce_ms.sweep`` reads that), averaged over
the cell's chips."""

from harness import spans


def read(ctx):
    return spans.scope_ms(ctx.trace, spans.pipeline_op_names(ctx.job),
                          r"run_chunks", "ufa_timeline",
                          "sweep.call", kernels=False)
