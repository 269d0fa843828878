"""Device time of the fused sweep program by named scope, and the part
under none, on a sweep cell's own load.

  python3 bench/tools/scope_coverage.py --workload hardened.sweep-64k \
      --seed 3000000019 --seconds 10 [--keep-trace <dir>]

A traced run of the cell as ``bench/run.py --trace 1`` makes it (the same
``harness.cell.run``: set-up, window, check; its result line is printed
first), then, per sweep call and averaged over the cell's chips:
``pipeline_xla_ms.sweep`` (the ``run_chunks`` programs outside their
kernels), the same time under each of the stage scopes (``spans.scope_ms``,
kernels left out) and the leaf ops under none (``spans.unscoped``), with
the costliest of those.  The scopes are read from the compiled program of
a job built again for the cell.  Refuses, as ``run.py`` does, without a TPU
or enough chips.  The last line is one JSON object.
"""

import argparse
import collections
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import run  # noqa: E402  (paths, the compile cache, the chip check)

SCOPES = ("ufa_dependency", "ufa_analytic", "ufa_timeline")


def coverage(trace, names, call: str) -> dict:
    from harness import spans, tracing

    prog = r"run_chunks"
    ops = spans.named_ops(trace, names, prog)
    xla = tracing.covered_s(ops) - tracing.covered_s(
        [e for e in ops if tracing.is_kernel(e)])
    per_call = 1e3 / max(1, len(trace.ops)) / max(
        1, tracing.calls_in_window(trace, call))
    rest = collections.Counter()
    for e in spans.unscoped(trace, names, prog, SCOPES):
        rest[f"{tracing.op_name(e)} | {spans.op_scope_name(e, names)}"] += (
            e.dur * per_call)
    unscoped = spans.unscoped_ms(trace, names, prog, SCOPES, call)
    return {"pipeline_xla_ms": xla * per_call,
            "by_scope_ms": {s: spans.scope_ms(trace, names, prog, s, call,
                                              kernels=False) for s in SCOPES},
            "unscoped_ms": unscoped,
            "unscoped_share": unscoped / (xla * per_call) if xla else None,
            "top_unscoped_ms": rest.most_common(10)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--keep-trace", default=None,
                    help="write the trace here and keep it")
    args = ap.parse_args(argv)

    import jax
    from harness import cell as cell_mod
    from harness import jobs, spans, spec, tracing

    cell = spec.find_cell(spec.load_benchmark(run.ROOT), args.workload,
                          run.ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    why = run.chips_present(cell.chips)
    if why:
        print(f"[scope_coverage] refused: {why}", file=sys.stderr)
        return 2
    keep = args.keep_trace or tempfile.mkdtemp(prefix="scope-trace-")
    result = cell_mod.run(cell, args.seed, args.seconds, True, run.T_PROCESS,
                          keep_trace=keep)
    print(json.dumps(result), flush=True)
    trace = tracing.load(keep)
    if args.keep_trace is None:
        shutil.rmtree(keep, ignore_errors=True)

    job = jobs.make(cell.config, cell.traffic, cell.chips, args.seed)
    job.setup()
    out = {"workload": args.workload, "seed": args.seed,
           "calls": tracing.calls_in_window(trace, job.span)}
    out.update(coverage(trace, spans.pipeline_op_names(job), job.span))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
