# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV; --json additionally writes rows + structured extras (per-scenario
# SLA verdicts, ...) for the perf trajectory (BENCH_*.json).
import argparse
import json
import os
import sys
import traceback

# allow `python benchmarks/run.py` as well as `python -m benchmarks.run`
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark names")
    ap.add_argument("--no-kernels", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="alias for --no-kernels (the kernel benches "
                    "dominate runtime) — the CI smoke configuration")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write rows + extras as JSON")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="enable the observability plane for the whole "
                    "run and write a Prometheus snapshot of the metrics "
                    "registry (bench rows included as "
                    "ufa_bench_us_per_call gauges)")
    args = ap.parse_args()
    args.no_kernels = args.no_kernels or args.quick

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.metrics_out:
        from repro import obs
        obs.enable()

    from benchmarks import bench_paper
    from benchmarks.common import EXTRAS, bench_meta, emit

    suites = list(bench_paper.ALL)
    if not args.no_kernels:
        from benchmarks import bench_kernels
        suites += bench_kernels.ALL

    print("name,us_per_call,derived")
    all_rows = []
    failures = 0
    for fn in suites:
        if args.only and args.only not in fn.__name__:
            continue
        try:
            rows = fn()
            emit(rows)
            all_rows.extend(rows)
        except Exception as e:
            failures += 1
            err_row = (fn.__name__, float("nan"),
                       f"ERROR {type(e).__name__}: {e}")
            print(f"{err_row[0]},nan,{err_row[2]}", file=sys.stdout)
            all_rows.append(err_row)
            traceback.print_exc(file=sys.stderr)

    if args.json:
        payload = {
            "meta": bench_meta(),
            # NaN (error rows) -> null: keep the artifact strict JSON
            "rows": [{"name": n,
                      "us_per_call": None if us != us else us,
                      "derived": d}
                     for n, us, d in all_rows],
            "extras": EXTRAS,
            "failures": failures,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.json}", file=sys.stderr)
    if args.metrics_out:
        from repro import obs
        from repro.obs import export
        for n, us, _ in all_rows:
            if us == us:                      # skip NaN error rows
                obs.set_gauge("ufa_bench_us_per_call", us, name=n)
        export.write_prometheus(args.metrics_out)
        print(f"wrote {args.metrics_out}", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
