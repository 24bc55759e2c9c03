"""1 minus the union of device-operation intervals over the traced part (profiler start to stop), averaged over the chips used. One quantity under two names, because its cells report different end-to-end metrics."""
from benchmarks.harness import trace_reduce


def read(ctx):
    return None if ctx.trace is None else trace_reduce.idle_share(ctx.trace, ctx.traced)
