"""Host milliseconds per sweep call inside ``ufa.sweep.fetch`` during which
the first device runs no op: the copies of the result columns to the host,
not the wait for the program to finish."""

from harness import spans


def read(ctx):
    return spans.idle_in_ms(ctx.trace, "ufa.sweep.fetch", "sweep.call")
