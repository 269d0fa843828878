"""Readings that the correctness limits are set from, at a cell's own size.

  python3 bench/tools/control.py --workload <cell> --seconds <s> --seeds 11 12 13

For each seed: set-up, a short window at the cell's load, then the numbers
the run compares, once for the program's answers and once for the control
(the plain reference at the next lower precision, bfloat16, or with
propagation cut to one sweep, in the program's place).  One JSON line per
seed: ``{"seed": ..., "program": {...}, "control": {...}}``.
"""

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--no-control", action="store_true",
                    help="read the program's numbers only")
    args = ap.parse_args(argv)

    import ml_dtypes
    import jax
    from harness import jobs, spec

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = spec.find_cell(spec.load_benchmark(ROOT), args.workload, ROOT)
    for seed in args.seeds:
        job = jobs.make(cell.config, cell.traffic, cell.chips, seed)
        job.setup()
        job.warm()
        t0, i = time.perf_counter(), 0
        while time.perf_counter() - t0 < args.seconds:
            job.call(i)
            i += 1
        job.release()
        out = {"seed": seed, "calls": i,
               "program": {n: v for n, v, _ in job.check()}}
        if not args.no_control:
            out["control"] = {n: v for n, v, _ in
                              job.check(control=ml_dtypes.bfloat16)}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
