"""Least time for one decode step's reads of its latent caches over the device time a step spends in them.

The need (``counts_dots3.latent_read_need``): the rows the mathematics reads,
``dsa_selected_rows`` x 1152 B in each full layer and ``swa_rows`` x 2176 B in
each window layer (the means of the traced ``serve/step`` spans), each once,
against a query's products with them. The time: device seconds of the
operations whose name holds the configuration's ``kernel_op`` (the
``latent_attention`` calls, one a layer) and that start inside a launch of the
step executable, over the number of launches. The kernel reads every row up to
a live stream's position, not the chosen ones (it cannot fetch single rows), so
this share is what a selected read would close. None where the trace shows no
such operation or the program stamps no ``dsa_*`` rows.
"""
from benchmarks.harness import counts, counts_dots3, step_ops

ROWS = ("dsa_selected_rows", "swa_rows")


def read(ctx):
    needle = ctx.config.get("kernel_op")
    if ctx.trace is None or not needle:
        return None
    by_needle, launches = step_ops.seconds_by_needle(ctx.trace, ctx.config["executables"]["step"], [needle])
    seconds = by_needle[needle]
    steps = [a for n, _, _, a in ctx.spans if n == "serve/step" and all(isinstance(a.get(k), (int, float)) for k in ROWS)]
    if not launches or seconds <= 0 or not steps:
        return None
    chosen, near = (sum(a[k] for a in steps) / len(steps) for k in ROWS)
    flops, nbytes = counts_dots3.latent_read_need(counts_dots3.shape(ctx.config), chosen, near, counts.dtype_bytes(ctx.config["cache_dtype"]))
    least = max(flops / (ctx.peaks["flops_per_s"] * ctx.chips), nbytes / (ctx.peaks["bytes_per_s"] * ctx.chips))
    ctx.notes["latent_read_ms_per_step"] = 1e3 * seconds / launches
    ctx.notes["latent_read_least_ms"] = 1e3 * least
    return 100.0 * least / (seconds / launches)
