"""Least time the chip could take for one decode step of an EvaByte style model over the time it took.

The least time is the larger of operations over peak FLOP/s and bytes over
peak bytes/s for what the algorithm needs (``counts_evabyte.decode_step_need``):
every weight once, K and V of the window rows and summaries that the live
streams READ (the means of ``eva_window_rows`` and ``eva_summary_rows`` of the
traced ``serve/step`` spans, not the tables' 3072 rows a slot), the rows
written (one window row a live stream, one summary row where a chunk closes:
``eva_chunks_closed``), one float32 row of every head's logits a live stream.
None where the program stamps no ``eva_*`` rows.
"""
from benchmarks.harness import counts, counts_evabyte, trace_reduce

ROWS = ("live", "eva_window_rows", "eva_summary_rows", "eva_chunks_closed")


def read(ctx):
    if ctx.trace is None or ctx.traced is None:
        return None
    step_ms = trace_reduce.executable_mean_ms(ctx, "step")
    steps = [a for n, _, _, a in ctx.spans if n == "serve/step" and all(isinstance(a.get(k), (int, float)) for k in ROWS)]
    if step_ms is None or not steps:
        return None
    live, near, far, closed = (sum(a[k] for a in steps) / len(steps) for k in ROWS)
    flops, nbytes = counts_evabyte.decode_step_need(
        counts_evabyte.shape(ctx.config), live, near, far, closed,
        counts.dtype_bytes(ctx.config["dtype"]), counts.dtype_bytes(ctx.config["cache_dtype"]))
    t_flops = flops / (ctx.peaks["flops_per_s"] * ctx.chips)
    t_bytes = nbytes / (ctx.peaks["bytes_per_s"] * ctx.chips)
    ctx.notes["decode_roofline_bound"] = "bytes" if t_bytes >= t_flops else "flops"
    ctx.notes["decode_least_ms"] = 1e3 * max(t_flops, t_bytes)
    ctx.notes["decode_need_gbytes"] = nbytes / 1e9
    return 100.0 * max(t_flops, t_bytes) / (step_ms * 1e-3)
