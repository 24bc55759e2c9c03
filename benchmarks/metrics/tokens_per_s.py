"""Generated tokens delivered to clients inside the window, over the window's seconds."""
from benchmarks.harness import window


def read(ctx):
    a, b = ctx.window
    return window.tokens_in(ctx.records, ctx.window) / (b - a)
