"""Least time for the routed experts' products of one decode step over the device time a step spends in them.

The need (``counts_dots3.expert_step_need``): the three matrices of every expert
HIT of the 32 held (mean ``experts_hit`` of the traced ``serve/step`` spans), once,
in each of the 4 routed layers, against the arithmetic of the assignments that fell
on them (mean ``assignments_held``). The time: device seconds of the operations
whose name holds the configuration's ``expert_op`` (the grouped product, three
calls a routed layer; the shared expert is a plain product beside them and is in
neither the need nor the time) and that start inside a launch of the step
executable, over the number of launches. None, never 0, where the trace shows no
such operation or the program stamps no ``experts_hit`` and ``assignments_held``.
"""
from benchmarks.harness import counts, counts_dots3, step_ops

ROWS = ("experts_hit", "assignments_held")


def read(ctx):
    needle = ctx.config.get("expert_op")
    if ctx.trace is None or not needle:
        return None
    by_needle, launches = step_ops.seconds_by_needle(ctx.trace, ctx.config["executables"]["step"], [needle])
    seconds = by_needle[needle]
    steps = [a for n, _, _, a in ctx.spans if n == "serve/step" and all(isinstance(a.get(k), (int, float)) for k in ROWS)]
    if not launches or seconds <= 0 or not steps:
        return None
    hit, held = (sum(a[k] for a in steps) / len(steps) for k in ROWS)
    flops, nbytes = counts_dots3.expert_step_need(counts_dots3.shape(ctx.config), held, hit, counts.dtype_bytes(ctx.config["dtype"]))
    least = max(flops / (ctx.peaks["flops_per_s"] * ctx.chips), nbytes / (ctx.peaks["bytes_per_s"] * ctx.chips))
    ctx.notes["expert_ms_per_step"] = 1e3 * seconds / launches
    ctx.notes["expert_least_ms"] = 1e3 * least
    return 100.0 * least / (seconds / launches)
