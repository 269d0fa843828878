"""Percent of the traced window in which no operation ran on the device
(mean over the cell's chips)."""

from harness import tracing


def read(ctx):
    return tracing.idle_share(ctx.trace)
