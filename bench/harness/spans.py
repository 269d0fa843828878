"""Reductions of the program's own spans and device scopes.

The program marks its host stages with ``repro.obs.span`` (names
``ufa.<layer>.<stage>``, see README "Observability"): each is a
``jax.profiler.TraceAnnotation``, so it lands in the device trace as a host
event on the trace's clock, with its counts as stats.  The fused sweep
program wraps its stages in ``jax.named_scope`` (``ufa_dependency``,
``ufa_analytic``, ``ufa_timeline``), which XLA keeps in each instruction's
``op_name`` metadata.  On the chip an op's trace event is its instruction's
HLO text without that metadata, so ``pipeline_op_names`` reads it from the
compiled program's text, by instruction name.  The metric readers under
``bench/metrics/`` divide what these reductions return by the calls of the
window; each returns ``None`` where the trace holds no such span or scope
(a program that does not mark it).
"""

from __future__ import annotations

import collections
import functools
import re
from typing import Dict, List, Optional, Tuple

from harness import tracing
from harness.tracing import Event, Trace

PREFIX = "ufa."
CLIENT = "client"            # in a call of the harness, in no ufa.* span
BETWEEN = "between calls"    # in the window, in no call

Intervals = List[Tuple[float, float]]


def spans(trace: Trace, name: str) -> List[Event]:
    """Host spans called ``name`` that lie inside the window."""
    return tracing.in_window(trace, (e for e in trace.host if e.name == name))


def per_call_ms(trace: Trace, seconds: float, call_span: str
                ) -> Optional[float]:
    calls = tracing.calls_in_window(trace, call_span)
    return 1e3 * seconds / calls if calls else None


def span_ms(trace: Trace, name: str, call_span: str) -> Optional[float]:
    """Milliseconds per call spent in spans ``name``."""
    found = spans(trace, name)
    if not found:
        return None
    return per_call_ms(trace, sum(e.dur for e in found), call_span)


def count_per_call(trace: Trace, name: str, call_span: str
                   ) -> Optional[float]:
    """Spans ``name`` per call."""
    found = spans(trace, name)
    calls = tracing.calls_in_window(trace, call_span)
    if not found or not calls:
        return None
    return len(found) / calls


def _overlap_s(a: Intervals, b: Intervals) -> float:
    """Seconds covered by both of two sorted lists of disjoint intervals."""
    tot, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_intervals(trace: Trace) -> Intervals:
    """The window's intervals in which the first device runs no op."""
    plane = sorted(trace.ops)[0]
    lo, hi = trace.window
    gaps, t = [], lo
    for a, b in tracing.union(tracing._clip(trace.ops[plane], lo, hi)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def idle_in_ms(trace: Trace, name: str, call_span: str) -> Optional[float]:
    """Milliseconds per call inside spans ``name`` during which the first
    device runs no op."""
    found = spans(trace, name)
    if not found or not trace.ops:
        return None
    inside = tracing.union([(e.start, e.end) for e in found])
    return per_call_ms(trace, _overlap_s(idle_intervals(trace), inside),
                       call_span)


def outside_ms(trace: Trace, call_span: str, inner: str) -> Optional[float]:
    """Milliseconds per call of ``call_span`` not covered by spans
    ``inner``."""
    found = spans(trace, inner)
    if not found:
        return None
    calls = tracing.union([(e.start, e.end) for e in spans(trace, call_span)])
    covered = tracing.union([(e.start, e.end) for e in found])
    total = sum(b - a for a, b in calls)
    return per_call_ms(trace, total - _overlap_s(calls, covered), call_span)


_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')
# ops that hand an operand on unchanged (or as a view of it)
_PASS_ON = ("get-tuple-element", "bitcast", "copy", "reshape")


def _balanced(s: str, i: int) -> int:
    """Index of the parenthesis closing the one at ``s[i]``."""
    depth = 0
    for j in range(i, len(s)):
        depth += {"(": 1, ")": -1}.get(s[j], 0)
        if depth == 0:
            return j
    return len(s)


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` metadata, from a compiled program's
    HLO text.  ``lax.map`` writes each chunk's results into its outputs
    with ``dynamic-update-slice`` ops that it emits outside the mapped
    function, so outside its named scopes: such a write takes the
    ``op_name`` of the instruction whose result it writes."""
    instrs = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        rest = line[m.end():]
        # the result's shape: a tuple in parentheses, else one token
        rest = (rest[_balanced(rest, 0) + 1:] if rest.startswith("(")
                else rest.partition(" ")[2]).lstrip()
        op = re.match(r"([\w\-]+)\(", rest)
        if not op:
            continue
        operands = re.findall(r"%([\w.\-]+)",
                              rest[op.end():_balanced(rest, op.end() - 1)])
        name = _OP_NAME.search(line)
        instrs[m.group(1)] = (op.group(1), operands,
                              name.group(1) if name else "")

    def written(n: str) -> str:
        for _ in range(8):
            opcode, operands, name = instrs.get(n, ("", [], ""))
            if name or opcode not in _PASS_ON or not operands:
                return name
            n = operands[0]
        return ""

    return {n: (written(operands[1]) or name)
            if opcode == "dynamic-update-slice" and len(operands) > 1
            else name for n, (opcode, operands, name) in instrs.items()}


@functools.lru_cache(maxsize=1)
def pipeline_op_names(job) -> Dict[str, str]:
    """``op_names`` of the fused sweep program that ``job`` (a sweep job)
    calls, from the compiled program's HLO text: the same executable its
    calls ran, found again in the compilation cache.  Empty for a job with
    no sweep engine."""
    engine = getattr(job, "engine", None)
    if engine is None:
        return {}
    fn, args, kw = engine._pipeline(job.grid(0))
    return op_names(fn.lower(*args, **kw).compile().as_text())


def _instruction(e: Event) -> Optional[str]:
    m = tracing._HLO.match(e.name)
    return m.group(1) if m else None


def op_scope_name(e: Event, names: Dict[str, str]) -> str:
    """The ``op_name`` of the instruction that op ``e`` ran."""
    return names.get(_instruction(e), "")


def named_ops(trace: Trace, names: Dict[str, str], program: str
              ) -> List[Event]:
    """The window's ops of the programs matching ``program``.  Each has to
    be an instruction of ``names``: names read from another executable than
    the one traced would charge ops to the wrong stage, so a traced op that
    ``names`` lacks raises (the harness then leaves the metric out and logs
    why)."""
    ops = tracing.program_ops(trace, program)
    missing = sorted({tracing.op_name(e) for e in ops
                      if _instruction(e) not in names})
    if missing:
        raise LookupError(
            f"{len(missing)} traced ops of {program!r} are not instructions "
            f"of the compiled program's text, e.g. {missing[:3]}")
    return ops


def scope_ms(trace: Trace, names: Dict[str, str], program: str, scope: str,
             call_span: str, kernels: bool) -> Optional[float]:
    """Device milliseconds per call under the ``jax.named_scope`` ``scope``
    (in the instruction's ``op_name`` from ``names``: ``.../<scope>/...``,
    or ``vmap(<scope>)`` where it was entered under a ``vmap``) in the
    programs matching ``program``, averaged over the devices: with
    ``kernels`` the Pallas kernels it calls included, else left out.  Ops
    nest (a ``while`` spans its body), so this is the time they cover."""
    ops = [e for e in named_ops(trace, names, program)
           if scope in op_scope_name(e, names)]
    if not ops:
        return None
    covered = tracing.covered_s(ops)
    if not kernels:
        covered -= tracing.covered_s([e for e in ops if tracing.is_kernel(e)])
    return per_call_ms(trace, covered / len(trace.ops), call_span)


def unscoped(trace: Trace, names: Dict[str, str], program: str, scopes
             ) -> List[Event]:
    """The leaf non-kernel ops of the programs matching ``program`` whose
    instruction lies under none of ``scopes``."""
    by_plane: Dict[str, List[Event]] = collections.defaultdict(list)
    for e in named_ops(trace, names, program):
        by_plane[e.thread].append(e)
    return [e for p in by_plane.values() for e in tracing.leaves(p)
            if not tracing.is_kernel(e)
            and not any(s in op_scope_name(e, names) for s in scopes)]


def unscoped_ms(trace: Trace, names: Dict[str, str], program: str, scopes,
                call_span: str) -> Optional[float]:
    """Device milliseconds per call of ``unscoped`` ops, averaged over the
    devices."""
    if not tracing.program_ops(trace, program):
        return None
    return per_call_ms(trace, tracing.covered_s(unscoped(
        trace, names, program, scopes)) / len(trace.ops), call_span)


def _segments(trace: Trace, call_span: str) -> List[Tuple[float, float, str]]:
    """The window cut into intervals, each named by the innermost ``ufa.*``
    span over it on the window's thread, else ``CLIENT`` inside a call and
    ``BETWEEN`` outside one."""
    thread = next(e.thread for e in trace.host
                  if e.name == tracing.WINDOW_SPAN)
    lo, hi = trace.window
    evs = [e for e in tracing.in_window(trace, trace.host)
           if e.thread == thread and e.dur > 0
           and (e.name.startswith(PREFIX) or e.name == call_span)]
    # starts sorted, outer spans first at a tie: the spans of one thread
    # nest, so a stack gives the innermost one at every instant
    evs.sort(key=lambda e: (e.start, -e.end))
    out: List[Tuple[float, float, str]] = []
    stack: List[Event] = []
    t = lo

    def emit(until: float):
        nonlocal t
        if until > t:
            name = BETWEEN
            if stack:
                top = stack[-1]
                name = CLIENT if top.name == call_span else top.name
            out.append((t, until, name))
            t = until

    for e in evs:
        while stack and stack[-1].end <= e.start:
            emit(stack[-1].end)
            stack.pop()
        emit(e.start)
        stack.append(e)
    while stack:
        emit(min(stack[-1].end, hi))
        stack.pop()
    emit(hi)
    return out


def idle_by_span(trace: Trace, call_span: str) -> List[List]:
    """The first device's idle seconds in the window, charged to the
    innermost ``ufa.*`` span over each instant (``CLIENT`` where only the
    harness's call span is), most first."""
    tot: Dict[str, float] = collections.Counter()
    segs = _segments(trace, call_span)
    idle = idle_intervals(trace)
    i = 0
    for a, b, name in segs:
        while i < len(idle) and idle[i][1] <= a:
            i += 1
        j = i
        while j < len(idle) and idle[j][0] < b:
            tot[name] += min(b, idle[j][1]) - max(a, idle[j][0])
            j += 1
    return [[name, s] for name, s in tot.most_common()]
