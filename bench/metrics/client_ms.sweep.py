"""Milliseconds per sweep call spent outside ``ufa.sweep.run``: the
harness's own scenario-grid draw and row sampling inside the timed call."""

from harness import spans


def read(ctx):
    return spans.outside_ms(ctx.trace, "sweep.call", "ufa.sweep.run")
