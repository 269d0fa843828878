"""Phase timing + JAX-aware pipeline profiling.

A ``Profiler`` times named phases of a pipeline run (detect, plan,
sweep, export, ...) into the ``ufa_phase_seconds`` histogram.  Each phase
is a host span (``repro.obs.trace.Span``): on the profiler's trace when
one is being taken, and on the host track of the attached tracer.
``phase(..., sync=tree)`` calls ``jax.block_until_ready`` on the tree
before stopping the clock, so async-dispatched device work is charged to
the phase that launched it instead of whoever touches the result first.

``jax`` is imported lazily (only when ``sync`` is actually used), so
the module itself stays importable in jax-free contexts.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Any, Dict

from repro import obs
from repro.obs.trace import Span


class Profiler:
    """Times phases into the registry (+ optional tracer host spans)."""

    def __init__(self, tracer=None):
        self.tracer = tracer if tracer is not None else obs.get_tracer()
        self.phases: Dict[str, float] = {}     # last wall time per phase

    @contextmanager
    def phase(self, name: str, sync: Any = None, **args):
        """Time a named phase.  ``sync`` is an optional pytree to
        ``block_until_ready`` before the clock stops."""
        t0 = time.perf_counter()
        span = Span(name, args, self.tracer).__enter__()
        try:
            yield self
        finally:
            if sync is not None:
                import jax
                jax.block_until_ready(sync)
            span.__exit__(None, None, None)
            dt = time.perf_counter() - t0
            self.phases[name] = dt
            obs.observe("ufa_phase_seconds", dt, phase=name)
