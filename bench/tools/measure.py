"""Run cells as the driver does, one process per run, and record every
result line (the parent never imports JAX, so each child owns the chips).

  python3 bench/tools/measure.py --out DIR --cells A B --seconds 10 \
      --sets 2 --seeds 11 12 13 14 15 16 --traced 21 22 23 --control 31 32 33

Per cell: ``--traced`` seeds with ``--trace 1`` (the first one's trace is
summarised by ``dump_trace.py`` into ``DIR/dump_<cell>.txt``, and its first
0.4 s exported to ``DIR/win_<cell>.json.gz``), ``--sets`` passes over
``--seeds`` with ``--trace 0``, then ``bench/tools/control.py`` on the
``--control`` seeds.  Appends one JSON line per run to
``DIR/results.jsonl``.
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
EXPORT = ("import sys; sys.path[:0] = ['bench']\n"
          "from harness import tracing\n"
          "tracing.export(tracing.load(sys.argv[1]), sys.argv[2], 0.4)\n")


def run(cmd, out_dir, tag, timeout):
    t0 = time.time()
    try:
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                           timeout=timeout)
        rc, out, err = p.returncode, p.stdout, p.stderr
    except subprocess.TimeoutExpired as e:
        rc, out, err = 124, e.stdout or "", e.stderr or ""
        out = out if isinstance(out, str) else out.decode()
        err = err if isinstance(err, str) else err.decode()
    with open(os.path.join(out_dir, tag + ".err"), "w") as f:
        f.write(err)
    return rc, time.time() - t0, out, err


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--cells", nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--traced", type=int, nargs="*", default=[])
    ap.add_argument("--control", type=int, nargs="*", default=[])
    ap.add_argument("--control-seconds", type=float, default=3)
    ap.add_argument("--timeout", type=float, default=360)
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    log = open(os.path.join(args.out, "results.jsonl"), "a")
    base = [sys.executable, "bench/run.py", "--seconds", str(args.seconds)]
    for cell in args.cells:
        plan = [(s, 1) for s in args.traced]
        plan += [(s, 0) for _ in range(args.sets) for s in args.seeds]
        for k, (seed, trace) in enumerate(plan):
            tag = f"{cell}.{seed}.t{trace}.{k}"
            cmd = base + ["--workload", cell, "--seed", str(seed),
                          "--trace", str(trace)]
            keep = None
            if trace and k == 0:
                keep = os.path.join(tempfile.mkdtemp(prefix="bench-keep-"), "tr")
                cmd += ["--keep-trace", keep]
            rc, dt, out, err = run(cmd, args.out, tag, args.timeout)
            lines = out.strip().splitlines()
            rec = {"cell": cell, "seed": seed, "trace": trace, "rc": rc,
                   "elapsed": dt,
                   "result": json.loads(lines[-1]) if rc == 0 and lines
                   else None,
                   "err_tail": err[-600:] if rc else ""}
            log.write(json.dumps(rec) + "\n")
            log.flush()
            r = rec["result"] or {}
            print(f"{tag} rc={rc} {dt:.1f}s correct={r.get('correct')} "
                  f"metrics={ {m: v['value'] for m, v in r.get('metrics', {}).items()} } "
                  f"check={ {m: v['value'] for m, v in r.get('check', {}).items()} }",
                  flush=True)
            if rc:
                print(err[-1500:], flush=True)
            if trace and k == 0 and rc == 0:
                with open(os.path.join(args.out, f"dump_{cell}.txt"), "w") as f:
                    subprocess.run([sys.executable, "bench/tools/dump_trace.py",
                                    keep, "30"], cwd=ROOT, stdout=f,
                                   stderr=subprocess.STDOUT, timeout=600)
                    subprocess.run([sys.executable, "-c", EXPORT, keep,
                                    os.path.join(args.out,
                                                 f"win_{cell}.json.gz")],
                                   cwd=ROOT, stdout=f,
                                   stderr=subprocess.STDOUT, timeout=600)
            if keep:
                shutil.rmtree(os.path.dirname(keep), ignore_errors=True)
        if args.control:
            cmd = [sys.executable, "bench/tools/control.py", "--workload",
                   cell, "--seconds", str(args.control_seconds), "--seeds",
                   *map(str, args.control)]
            rc, dt, out, err = run(cmd, args.out, f"{cell}.control",
                                   args.timeout * len(args.control))
            for line in out.strip().splitlines():
                log.write(json.dumps({"cell": cell, "control": json.loads(line)})
                          + "\n")
            print(f"{cell} control rc={rc} {dt:.1f}s\n{out}", flush=True)
            if rc:
                print(err[-1500:], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
