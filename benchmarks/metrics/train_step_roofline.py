"""Least time the chips could take for one train step over the time it took.

The least time is the step's forward-and-backward operations from the layer
shapes (``counts.resnet_train_step_flops``: two per multiply-add, the
backward pass twice the forward) over peak FLOP/s x chips. Compute bounds
it: the weights and the activations kept for the backward pass are two
orders of magnitude fewer bytes per operation than the chip's balance.
"""
from benchmarks.harness import counts, trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    step_ms = trace_reduce.executable_mean_ms(ctx, "step")
    if step_ms is None:
        return None
    kw, feed = ctx.config["constructor_kwargs"], ctx.config["feed"]
    flops = counts.resnet_train_step_flops(ctx.counters["batch"], kw["depth"], feed["crop"], kw["class_num"])
    ctx.notes["train_step_roofline_bound"] = "flops"
    return 100.0 * flops / (ctx.peaks["flops_per_s"] * ctx.chips) / (step_ms * 1e-3)
