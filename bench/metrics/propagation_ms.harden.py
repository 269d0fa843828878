"""Device milliseconds per hardening job in the ELL propagation kernel
(``kernels/ufa/propagation``): the Pallas calls inside the
``fixed_point_ell`` programs, every round of certify, the ensemble and the
planner's batches, averaged over the cell's chips."""

from harness import tracing


def read(ctx):
    ops = [e for e in tracing.program_ops(ctx.trace, r"fixed_point_ell")
           if tracing.is_kernel(e)]
    jobs = tracing.calls_in_window(ctx.trace, "harden.job")
    if not ops or not jobs:
        return None
    return 1e3 * sum(e.dur for e in ops) / len(ctx.trace.ops) / jobs
