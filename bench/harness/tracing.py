"""Device trace of a window, and its reduction to per-layer numbers.

``capture`` records a ``jax.profiler`` trace; ``load`` reads the
``.xplane.pb`` it wrote into plain events (seconds on the trace's clock):

  * per device plane, the operations that ran on it (the ``XLA Ops``
    line) and the programs they ran in (``XLA Modules``), each with the
    plane's name as its ``thread``;
  * the host's spans (``TraceAnnotation`` and the runtime's own), each
    with its thread.

The reductions below (busy time, kernel time, top operations, idle gaps by
host span) are what the metric readers under ``bench/metrics/`` call.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str
    start: float          # seconds
    end: float
    stats: Dict[str, object]
    thread: str = ""

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]        # device plane -> operations
    modules: Dict[str, List[Event]]    # device plane -> programs
    host: List[Event]                  # host spans, all threads
    window: Tuple[float, float]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


@contextlib.contextmanager
def capture(log_dir: str):
    import jax
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def xplane_path(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _events(line, thread: str = "") -> Iterable[Event]:
    for e in line.events:
        start = e.start_ns * 1e-9
        yield Event(e.name, start, start + e.duration_ns * 1e-9,
                    dict(e.stats), thread)


def load(path: str) -> Trace:
    """Read a trace directory (or ``.xplane.pb``) written by ``capture``, or
    a ``.json.gz`` extract written by ``export``."""
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        ev = lambda rows: [Event(*r) for r in rows]
        return Trace(ops={k: ev(v) for k, v in d["ops"].items()},
                     modules={k: ev(v) for k, v in d["modules"].items()},
                     host=ev(d["host"]), window=tuple(d["window"]))
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = xplane_path(path)
    pd = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:") and OPS_LINE in lines:
            ops[plane.name] = sorted(_events(lines[OPS_LINE], plane.name),
                                     key=lambda e: e.start)
            modules[plane.name] = (sorted(_events(lines[MODULES_LINE],
                                                  plane.name),
                                          key=lambda e: e.start)
                                   if MODULES_LINE in lines else [])
        elif plane.name.startswith("/host:"):
            for name, line in lines.items():
                host.extend(_events(line, name))
    win = [e for e in host if e.name == WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    return Trace(ops=ops, modules=modules, host=host,
                 window=(win[0].start, win[0].end))


def export(trace: Trace, path: str, seconds: Optional[float] = None):
    """Write the window (its first ``seconds`` when given) as a small
    ``.json.gz`` that ``load`` reads back: a recorded trace for tests."""
    lo = trace.window[0]
    hi = trace.window[1] if seconds is None else min(trace.window[1],
                                                      lo + seconds)
    keep = lambda evs: [[e.name, e.start, e.end, {
        k: v for k, v in e.stats.items() if isinstance(v, (int, float, str))},
        e.thread] for e in evs if e.start < hi and e.end > lo]
    d = {"ops": {k: keep(v) for k, v in trace.ops.items()},
         "modules": {k: keep(v) for k, v in trace.modules.items()},
         "host": keep(trace.host), "window": [lo, hi]}
    with gzip.open(path, "wt") as f:
        json.dump(d, f)


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def _clip(events: Iterable[Event], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    return sorted((max(e.start, lo), min(e.end, hi)) for e in events
                  if e.end > lo and e.start < hi)


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(trace: Trace, plane: Optional[str] = None) -> float:
    """Seconds of the window in which an operation ran on the device
    (``plane``), or the mean over the traced devices."""
    planes = [plane] if plane else sorted(trace.ops)
    if not planes:
        return 0.0
    lo, hi = trace.window
    tot = 0.0
    for p in planes:
        tot += sum(b - a for a, b in union(_clip(trace.ops[p], lo, hi)))
    return tot / len(planes)


def covered_s(events: Iterable[Event]) -> float:
    """Seconds covered by ``events``, summed over the devices (``thread``)
    they ran on, counting nested and overlapping ops once."""
    by_plane: Dict[str, List[Tuple[float, float]]] = collections.defaultdict(
        list)
    for e in events:
        by_plane[e.thread].append((e.start, e.end))
    return sum(b - a for iv in by_plane.values() for a, b in union(iv))


def idle_share(trace: Trace) -> Optional[float]:
    """Percent of the window in which no operation ran (mean over devices)."""
    if not trace.ops or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - busy_s(trace) / trace.window_s)


def text(e: Event) -> str:
    """All an operation says of itself: its name (on a TPU the HLO text of
    the instruction) and its string stats (``long_name``, ``tf_op``, ...)."""
    return " ".join([e.name] + [v for v in e.stats.values()
                                if isinstance(v, str)])


_HLO = re.compile(r"%?([\w.\-]+) = ")


def op_name(e: Event) -> str:
    """A short name for an operation: a Pallas kernel by its wrapper
    (``jit(<kernel>)/pallas_call`` in the op's metadata), anything else by
    its HLO instruction name and the first shape of its result, where the
    event carries the instruction's text (``fusion.127 f32[4096,240]``)."""
    m = re.search(r"jit\((\w+)\)/pallas_call", text(e))
    if m:
        return m.group(1)
    m = _HLO.match(e.name)
    if not m:
        return e.name[:120]
    shape = _SHAPE.search(e.name, m.end())
    return m.group(1) + (f" {shape.group(0)}" if shape else "")


def in_window(trace: Trace, events: Iterable[Event]) -> List[Event]:
    lo, hi = trace.window
    return [e for e in events if e.start >= lo and e.end <= hi]


def module_events(trace: Trace, pattern: str, plane: Optional[str] = None
                  ) -> List[Event]:
    rx = re.compile(pattern)
    planes = [plane] if plane else sorted(trace.modules)
    return [e for p in planes for e in in_window(trace, trace.modules[p])
            if rx.search(e.name)]


def leaves(events: List[Event]) -> List[Event]:
    """The ops of one device that contain no other op: a ``while`` or a
    call spans its body's ops and is left out, so no time counts twice."""
    evs = sorted(events, key=lambda e: (e.start, -e.end))
    return [e for e, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or nxt.start >= e.end or nxt.end > e.end]


def top_ops(trace: Trace, k: int = 10) -> List[List]:
    """The ``k`` operation names with the most device time in the window,
    leaf ops only (summed over devices, divided by their number)."""
    tot = collections.Counter()
    for p in trace.ops:
        for e in leaves(in_window(trace, trace.ops[p])):
            tot[op_name(e)] += e.dur
    n = max(1, len(trace.ops))
    return [[name, s / n] for name, s in tot.most_common(k)]


def idle_gaps(trace: Trace, k: int = 10) -> List[List]:
    """Idle time of the first device in the window, by what the host was
    doing: each gap is charged to the innermost span, on the thread that
    ran the window, that covers its midpoint (``idle`` where none does);
    the ``k`` names with the most."""
    if not trace.ops:
        return []
    plane = sorted(trace.ops)[0]
    lo, hi = trace.window
    busy = union(_clip(trace.ops[plane], lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    thread = next(e.thread for e in trace.host if e.name == WINDOW_SPAN)
    spans = sorted((e for e in trace.host
                    if e.thread == thread and e.end > lo and e.start < hi
                    and e.dur > 0 and e.name != WINDOW_SPAN),
                   key=lambda e: e.start)
    starts = [e.start for e in spans]
    tot = collections.Counter()
    for a, b in gaps:
        mid = 0.5 * (a + b)
        # spans of one thread nest: the latest-starting span that covers
        # the midpoint is the innermost
        name = "idle"
        for i in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            if spans[i].end >= mid:
                name = spans[i].name
                break
        tot[name] += b - a
    return [[name, s] for name, s in tot.most_common(k)]


def is_kernel(e: Event) -> bool:
    """Is ``e`` a Pallas kernel: a Mosaic custom call, or an op whose
    metadata names a ``pallas_call``?  (An op that only reads a kernel's
    result names its operand ``%pallas_call.N`` and is not one.)"""
    return ('custom_call_target="tpu_custom_call"' in text(e)
            or "pallas_call" in str(e.stats.get("tf_op", "")))


def kernel_events(trace: Trace, kernel: str) -> List[Event]:
    """Window events of the Pallas kernel whose wrapper is ``kernel`` (its
    name appears in the op's text or metadata, as the HLO instruction's
    name or as ``jit(<kernel>)/pallas_call``)."""
    rx = re.compile(r"\b" + re.escape(kernel) + r"\b")
    return [e for p in sorted(trace.ops) for e in in_window(trace, trace.ops[p])
            if is_kernel(e) and rx.search(text(e))]


_SHAPE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")


def operand_shapes(e: Event) -> List[Tuple[int, ...]]:
    """Operand shapes of a custom call, from the HLO text the event carries
    (``operand_layout_constraints={f32[4096,240]{1,0}, ...}``)."""
    t = text(e)
    key = "operand_layout_constraints={"
    start = t.find(key)
    if start < 0:
        return []
    i = start + len(key)
    depth = 1
    for j in range(i, len(t)):
        depth += {"{": 1, "}": -1}.get(t[j], 0)
        if depth == 0:
            break
    return [tuple(int(d) for d in dims.split(",") if d)
            for _, dims in _SHAPE.findall(t[i:j])]


def program_ops(trace: Trace, pattern: str) -> List[Event]:
    """Window operations that ran inside a program whose name matches
    ``pattern``: the op's ``hlo_module`` stat where it has one, else the
    ``XLA Modules`` event on its device that contains it."""
    rx = re.compile(pattern)
    out = []
    for p in sorted(trace.ops):
        mods = trace.modules.get(p, [])
        starts = [m.start for m in mods]
        for e in in_window(trace, trace.ops[p]):
            name = str(e.stats.get("hlo_module", ""))
            if not name:
                i = bisect.bisect_right(starts, e.start) - 1
                if i >= 0 and mods[i].end >= e.end:
                    name = mods[i].name
            if rx.search(name):
                out.append(e)
    return out


def calls_in_window(trace: Trace, span: str) -> int:
    lo, hi = trace.window
    return sum(1 for e in trace.host
               if e.name == span and e.start >= lo and e.end <= hi)

