"""Host milliseconds per detection job in ``ufa.detect.verdicts``: the
found, true and cold edge sets and the detection graph built from the
mask."""

from harness import spans


def read(ctx):
    return spans.span_ms(ctx.trace, "ufa.detect.verdicts", "detect.job")
