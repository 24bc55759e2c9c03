"""Mean device duration of the train step executable's launches in the traced part."""
from benchmarks.harness import trace_reduce


def read(ctx):
    return trace_reduce.executable_mean_ms(ctx, "step")
