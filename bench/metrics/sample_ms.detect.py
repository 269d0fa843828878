"""Device milliseconds per trace-sampling program run
(``dependency._sample_kernel``: one 4M-record chunk of alias-method edge
draws and Bernoulli outcomes)."""

from harness import tracing


def read(ctx):
    ops = tracing.program_ops(ctx.trace, r"_sample_kernel")
    runs = len(tracing.module_events(ctx.trace, r"_sample_kernel"))
    if not ops or not runs:
        return None
    return 1e3 * sum(e.dur for e in ops) / runs
