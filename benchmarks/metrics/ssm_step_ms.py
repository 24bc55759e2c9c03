"""Device milliseconds a launch of the step executable spends in the state kernel: the operations whose name holds the configuration's ``ssm_op`` (``ssm_step``, one call a Mamba layer) and that start inside a launch, summed over its Mamba layers, over the number of launches. None where the trace shows no such operation."""
from benchmarks.harness import step_ops


def read(ctx):
    needle = ctx.config.get("ssm_op")
    if ctx.trace is None or not needle:
        return None
    seconds, launches = step_ops.seconds_by_needle(ctx.trace, ctx.config["executables"]["step"], [needle])
    if not launches or seconds[needle] <= 0:
        return None
    return 1e3 * seconds[needle] / launches
