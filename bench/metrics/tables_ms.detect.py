"""Host milliseconds per detection job in ``ufa.detect.tables``: the trace
edges, their sampling weights and the alias tables, built and uploaded, and
the detector's empty counts."""

from harness import spans


def read(ctx):
    return spans.span_ms(ctx.trace, "ufa.detect.tables", "detect.job")
