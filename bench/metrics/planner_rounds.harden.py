"""Greedy planner rounds per hardening job (``ufa.planner.round`` spans,
the certifying round included): a work count, so a change can tell fewer
rounds from cheaper ones."""

from harness import spans


def read(ctx):
    return spans.count_per_call(ctx.trace, "ufa.planner.round", "harden.job")
