"""Least time for what the state kernel moves in a step over the device time a step spends in it.

The need (``counts_nemotron3.ssm_step_need``): for the live slots the traced ``serve/step`` spans stamp (``ssm_slots``, their mean),
every Mamba layer's S read and written in float32, and the ``x``, ``B``, ``C`` and ``dt`` rows it is updated from and the ``y`` it gives,
against the recurrence's operations. The time: device seconds of the operations whose name holds the configuration's ``ssm_op`` that start
inside a launch of the step executable, over the number of launches. None where the trace shows no such operation or the program stamps no
``ssm_slots``.
"""
from benchmarks.harness import counts_nemotron3, step_ops


def read(ctx):
    needle = ctx.config.get("ssm_op")
    if ctx.trace is None or not needle:
        return None
    by_needle, launches = step_ops.seconds_by_needle(ctx.trace, ctx.config["executables"]["step"], [needle])
    seconds = by_needle[needle]
    slots = [a["ssm_slots"] for n, _, _, a in ctx.spans if n == "serve/step" and isinstance(a.get("ssm_slots"), (int, float))]
    if not launches or seconds <= 0 or not slots:
        return None
    flops, nbytes = counts_nemotron3.ssm_step_need(counts_nemotron3.shape(ctx.config), sum(slots) / len(slots))
    least = max(flops / (ctx.peaks["flops_per_s"] * ctx.chips), nbytes / (ctx.peaks["bytes_per_s"] * ctx.chips))
    ctx.notes["ssm_step_least_ms"] = 1e3 * least
    ctx.notes["ssm_step_gbytes"] = nbytes / 1e9
    return 100.0 * least / (seconds / launches)
