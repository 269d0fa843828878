"""95th percentile of the per-call sweep latency over every call of the
window (linear interpolation between order statistics)."""

import numpy as np


def read(ctx):
    return float(np.percentile([b - a for a, b, _ in ctx.calls], 95))
