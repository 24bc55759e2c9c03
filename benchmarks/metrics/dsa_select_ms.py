"""Device milliseconds a launch of the step executable spends choosing and fetching what a full layer reads: the operations whose name holds one of the configuration's ``select_ops`` (the index product, the threshold's passes and its tie branch, the read of the chosen latents) and that start inside a launch, summed over its full layers, over the number of launches. ``notes`` give the same a needle. None where the trace shows no such operation."""
from benchmarks.harness import step_ops


def read(ctx):
    needles = ctx.config.get("select_ops")
    if ctx.trace is None or not needles:
        return None
    seconds, launches = step_ops.seconds_by_needle(ctx.trace, ctx.config["executables"]["step"], needles)
    total = sum(seconds.values())
    if not launches or total <= 0:
        return None
    ctx.notes["dsa_select_ms_by_op"] = {k: 1e3 * v / launches for k, v in seconds.items()}
    return 1e3 * total / launches
