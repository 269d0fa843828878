"""Device milliseconds per sweep call in the fused pipeline program
(``_run_chunks_dep``) outside its Pallas kernels: the analytic stage, the
timeline series and the gathers, averaged over the cell's chips.  Device
ops nest (a ``while`` spans its body), so this is the time covered by the
program's ops less the time covered by its kernels."""

from harness import tracing


def read(ctx):
    ops = tracing.program_ops(ctx.trace, r"run_chunks")
    calls = tracing.calls_in_window(ctx.trace, "sweep.call")
    if not ops or not calls:
        return None
    covered = tracing.covered_s(ops) - tracing.covered_s(
        [e for e in ops if tracing.is_kernel(e)])
    return 1e3 * covered / len(ctx.trace.ops) / calls
