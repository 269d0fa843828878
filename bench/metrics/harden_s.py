"""The window over the hardening jobs it completed (certify, ensemble,
plan to certification): seconds per job."""


def read(ctx):
    return ctx.window_s / len(ctx.calls)
