"""Least time for the expert products of one decode step over the device time a step spends in them.

The need (``counts_lfm2moe.expert_step_need``): the three matrices of every
expert HIT (mean ``experts_hit`` of the traced ``serve/step`` spans), once,
in every routed layer, against ``live`` x ``num_experts_per_tok`` experts'
arithmetic. The time: device seconds of the operations whose name holds the
configuration's ``expert_op`` and that start inside a launch of the step
executable, over the number of those launches. None, never 0, where the
trace shows no such operation or the program stamps no ``experts_hit``.
"""
import bisect

from benchmarks.harness import counts, counts_lfm2moe, trace_reduce


def step_expert_seconds(red, step_module, needle):
    """``(seconds, launches)`` on the lowest device: the summed duration of
    the operations named ``*needle*`` that start inside a launch of
    ``step_module``, and the number of such launches."""
    devs = red.devices()
    if not devs:
        return 0.0, 0
    steps = [(s, s + d) for n, s, d in red.modules.get(devs[0], ())
             if trace_reduce.module_name(n) == step_module]
    starts = [s for s, _ in steps]
    total = 0.0
    for n, s, d in red.ops.get(devs[0], ()):
        if needle in n:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < steps[i][1]:
                total += d
    return total, len(steps)


def read(ctx):
    needle = ctx.config.get("expert_op")
    if ctx.trace is None or not needle:
        return None
    seconds, launches = step_expert_seconds(
        ctx.trace, ctx.config["executables"]["step"], needle)
    steps = [a for n, _, _, a in ctx.spans if n == "serve/step"
             and isinstance(a.get("experts_hit"), (int, float))]
    if not launches or seconds <= 0 or not steps:
        return None
    hit = sum(a["experts_hit"] for a in steps) / len(steps)
    live = sum(a.get("live", 0) for a in steps) / len(steps)
    flops, nbytes = counts_lfm2moe.expert_step_need(
        counts_lfm2moe.shape(ctx.config), live, hit,
        counts.dtype_bytes(ctx.config["dtype"]))
    least = max(flops / (ctx.peaks["flops_per_s"] * ctx.chips),
                nbytes / (ctx.peaks["bytes_per_s"] * ctx.chips))
    ctx.notes["expert_ms_per_step"] = 1e3 * seconds / launches
    ctx.notes["expert_least_ms"] = 1e3 * least
    return 100.0 * least / (seconds / launches)
