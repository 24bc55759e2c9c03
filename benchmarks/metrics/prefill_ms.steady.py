"""Mean device duration of the prefill executable's launches (every bucket) in the traced part. One quantity under two names, because its cells report different end-to-end metrics."""
from benchmarks.harness import trace_reduce


def read(ctx):
    return trace_reduce.executable_mean_ms(ctx, "prefill")
