"""Records sampled, ingested and detected in whole ``runtime_analysis``
jobs (detect mask and detection graph included), over the window."""


def read(ctx):
    return sum(units for _, _, units in ctx.calls) / ctx.window_s
