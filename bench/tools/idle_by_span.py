"""Charge the first device's idle time in a traced window to the program's
stages.

  python3 bench/tools/idle_by_span.py <trace dir written by --keep-trace>

Each idle instant of the window goes to the innermost ``ufa.*`` span over
it on the window's thread; an instant inside a call of the harness and in
no ``ufa.*`` span goes to "client", one between calls to "between calls".
The last line is the same as JSON.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from harness import spans, tracing  # noqa: E402

CALL_SPANS = ("sweep.call", "detect.job", "harden.job")


def report(trace: tracing.Trace) -> dict:
    names = {e.name for e in trace.host}
    call = next(c for c in CALL_SPANS if c in names)
    rows = spans.idle_by_span(trace, call)
    idle = sum(s for _, s in rows)
    marked = sum(s for n, s in rows
                 if n.startswith(spans.PREFIX) or n == spans.CLIENT)
    return {"call_span": call, "calls": tracing.calls_in_window(trace, call),
            "window_s": trace.window_s, "idle_s": idle,
            "marked_share": marked / idle if idle else None,
            "idle_by_span": rows}


def main(path: str) -> None:
    r = report(tracing.load(path))
    print(f"{r['calls']} {r['call_span']} calls, window {r['window_s']:.3f} s,"
          f" device idle {r['idle_s']:.3f} s")
    for name, s in r["idle_by_span"]:
        print(f"  {s:9.4f} s  {100 * s / r['idle_s']:6.2f}%  {name}")
    print(f"  under a ufa.* span or client: {100 * r['marked_share']:.2f}%")
    print(json.dumps(r))


if __name__ == "__main__":
    main(sys.argv[1])
