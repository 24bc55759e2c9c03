"""Forward-and-backward operations of every image whose step completed in the traced part, over its seconds x chips x peak FLOP/s."""
from benchmarks.harness import counts


def read(ctx):
    if ctx.trace is None or ctx.traced is None:
        return None
    a, b = ctx.traced
    steps = sum(1 for t, _ in getattr(ctx, "steps", ()) if a <= t < b)
    if not steps:
        return None
    kw, feed = ctx.config["constructor_kwargs"], ctx.config["feed"]
    flops = steps * counts.resnet_train_step_flops(ctx.counters["batch"], kw["depth"], feed["crop"], kw["class_num"])
    return 100.0 * flops / ((b - a) * ctx.chips * ctx.peaks["flops_per_s"])
