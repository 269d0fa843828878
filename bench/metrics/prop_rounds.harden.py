"""Propagation rounds per hardening job: the ``rounds`` counts of the
window's ``ufa.graph.certify`` and ``ufa.graph.ensemble`` spans (each
fixed point's rounds, the unchanged last one included), over the jobs.
None where the program does not count them."""

from harness import spans, tracing

SPANS = ("ufa.graph.certify", "ufa.graph.ensemble")


def read(ctx):
    found = [e for name in SPANS for e in spans.spans(ctx.trace, name)
             if "rounds" in e.stats]
    jobs = tracing.calls_in_window(ctx.trace, "harden.job")
    if not found or not jobs:
        return None
    return sum(float(e.stats["rounds"]) for e in found) / jobs
