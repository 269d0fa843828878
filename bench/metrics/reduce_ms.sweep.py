"""Device milliseconds per sweep call in the timeline verdict-reduction
kernel (``kernels/ufa/reduce``, the ``timeline_reduce`` Pallas call), summed
over its chunk calls and averaged over the cell's chips."""

from harness import tracing


def read(ctx):
    ops = tracing.kernel_events(ctx.trace, "timeline_reduce")
    calls = tracing.calls_in_window(ctx.trace, "sweep.call")
    if not ops or not calls:
        return None
    return 1e3 * sum(e.dur for e in ops) / len(ctx.trace.ops) / calls
