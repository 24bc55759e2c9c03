"""95th percentile over every gap between consecutive tokens of every stream, for tokens the clients received inside the window."""
from benchmarks.harness import window


def read(ctx):
    v = window.percentile(window.token_gaps(ctx.records, ctx.window), 95)
    return None if v is None else v * 1e3
