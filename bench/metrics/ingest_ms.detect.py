"""Device milliseconds per run of the histogram ingest kernel
(``kernels/ufa/ingest``, the ``ingest_hist`` Pallas call): one per chunk of
sampled records, the short last chunk of each job included."""

from harness import tracing


def read(ctx):
    ops = tracing.kernel_events(ctx.trace, "ingest_hist")
    if not ops:
        return None
    return 1e3 * sum(e.dur for e in ops) / len(ops)
