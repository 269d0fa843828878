"""Set-up time: process start, fleet synthesis, and compiling (or loading
from the cache) and running every shape once, up to the window."""


def read(ctx):
    return ctx.setup_s
