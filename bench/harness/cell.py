"""One run of a cell: set-up, the measured window, the optional device
trace, the correctness check, and the result line."""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import shutil
import sys
import tempfile
import time
import traceback
from typing import Dict, List, Optional, Tuple

from harness import check as chk
from harness import jobs
from harness import spec as spec_mod
from harness import tracing


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""
    cell: spec_mod.Cell
    calls: List[Tuple[float, float, float]]     # (start, end, work units)
    window_s: float
    setup_s: float
    device_kind: str
    trace: Optional[tracing.Trace] = None
    job: Optional[jobs.Job] = None


class CompileCounter:
    """Counts JAX tracing, compilation and persistent-cache reads while
    ``active``.  A compile or a cache read inside the window means a shape
    was not warmed up; a trace alone (a small eager op whose jaxpr is
    rebuilt, then found in the in-memory cache) compiles nothing."""

    NAMES = {"/jax/core/compile/jaxpr_trace_duration": "traces",
             "/jax/core/compile/backend_compile_duration": "compiles",
             "/jax/compilation_cache/cache_retrieval_time_sec": "cache reads"}

    def __init__(self):
        import jax
        self.active = False
        self.counts = dict.fromkeys(self.NAMES.values(), 0)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if self.active and event in self.NAMES:
            self.counts[self.NAMES[event]] += 1


def _peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def run(cell: spec_mod.Cell, seed: int, seconds: float, trace: bool,
        t_process: float, keep_trace: Optional[str] = None,
        log=sys.stderr) -> Dict:
    import jax

    devices = jax.devices()[:cell.chips]
    kind = devices[0].device_kind
    counter = CompileCounter()
    job = jobs.make(cell.config, cell.traffic, cell.chips, seed)
    job.setup()
    print(f"[bench] fleet {job.describe()}", file=log, flush=True)
    job.warm()
    # what set-up built lives on: keep the collector from rescanning it
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_process
    print(f"[bench] {cell.name}: set-up {setup_s:.3f} s", file=log, flush=True)

    trace_dir = keep_trace or (tempfile.mkdtemp(prefix="bench-trace-")
                               if trace else None)
    calls: List[Tuple[float, float, float]] = []
    counter.active = True
    with (tracing.capture(trace_dir) if trace else contextlib.nullcontext()):
        with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            t_start = time.perf_counter()
            i = 0
            while time.perf_counter() - t_start < seconds:
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation(job.span):
                    units = job.call(i)
                calls.append((t0, time.perf_counter(), units))
                i += 1
    counter.active = False
    window_s = calls[-1][1] - t_start
    print(f"[bench] window {window_s:.3f} s, {len(calls)} {job.span} calls, "
          f"{sum(c[2] for c in calls):.0f} {job.unit}; inside it "
          + ", ".join(f"{n} {k}" for k, n in counter.counts.items()),
          file=log, flush=True)
    peak = _peak_bytes(devices)

    tr = None
    if trace:
        tr = tracing.load(trace_dir)
        if keep_trace is None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    ctx = Context(cell=cell, calls=calls, window_s=window_s, setup_s=setup_s,
                  device_kind=kind, trace=tr, job=job)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        try:
            value = spec_mod.reader(m["name"])(ctx)
        except Exception:  # a reader that fails leaves its metric out
            traceback.print_exc(file=log)
            continue
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    job.release()
    gc.unfreeze()
    gc.collect()
    t0 = time.perf_counter()
    items = job.check()
    print(f"[bench] check took {time.perf_counter() - t0:.3f} s", file=log,
          flush=True)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": chk.passed(items), "attempted": len(calls),
              "failed": 0, "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = tracing.busy_s(tr)
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tracing.top_ops(tr),
                               "idle_gaps": tracing.idle_gaps(tr)}
    result["check"] = chk.line(items)
    for name, value, limit in items:
        print(f"check {name} = {value!r} limit {limit!r}", file=log)
    return result
