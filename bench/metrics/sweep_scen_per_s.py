"""Scenarios whose verdicts reached the host in the window, over the
window (from the first call's start to the last call's end)."""


def read(ctx):
    return sum(units for _, _, units in ctx.calls) / ctx.window_s
