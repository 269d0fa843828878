"""Host milliseconds per sweep call in ``ufa.sweep.prepare``: grid
validation, bucketing, padding the scenario axes, the unique dark sets and
their upload, before the pipeline is dispatched."""

from harness import spans


def read(ctx):
    return spans.span_ms(ctx.trace, "ufa.sweep.prepare", "sweep.call")
