"""Milliseconds per hardening job inside ``ufa.planner.plan`` during which
the device runs no op: the planner's host loop and its dispatches."""

from harness import spans


def read(ctx):
    return spans.idle_in_ms(ctx.trace, "ufa.planner.plan", "harden.job")
