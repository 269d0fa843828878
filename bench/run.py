"""Benchmark of the UFA failover pipeline on TPU: one cell per run.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration, traffic mix
and metrics are named in ``BENCHMARK.json`` and found by name under
``bench/`` (see ``bench/harness/spec.py``).  Set-up builds the fleet from
its configuration and compiles every shape the traffic uses; the window
then drives calls back to back for ``--seconds`` (a call that starts
inside it runs to its end, and the window ends with it).  ``--trace 1``
records a device trace of the window and reports the per-layer metrics in
place of the end-to-end ones.  After the window a seeded sample of the
answers is compared with the plain references in ``bench/harness/
reference.py``; the numbers compared and their limits are the last lines
on standard error and the ``check`` entry of the result.

The last line on standard output is the result, one JSON object.  The run
exits non-zero, with no result, where JAX finds no TPU or fewer chips than
the cell asks for.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
# the persistent compilation cache lives at a fixed path in the checkout
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="write the trace here and keep it")
    return ap.parse_args(argv)


def chips_present(n: int) -> str:
    """Why this machine cannot run an ``n``-chip cell ('' when it can)."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return f"JAX found no TPU (platform {devices[0].platform!r})"
    if len(devices) < n:
        return f"the cell needs {n} chips, JAX found {len(devices)}"
    return ""


def main(argv=None) -> int:
    args = parse(argv)
    import repro  # noqa: F401  (the system under test, from the checkout)
    from harness import cell as cell_mod
    from harness import spec

    cell = spec.find_cell(spec.load_benchmark(ROOT), args.workload, ROOT)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    why = chips_present(cell.chips)
    if why:
        print(f"[bench] refused: {why}", file=sys.stderr)
        return 2
    result = cell_mod.run(cell, args.seed, args.seconds, bool(args.trace),
                          T_PROCESS, keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
