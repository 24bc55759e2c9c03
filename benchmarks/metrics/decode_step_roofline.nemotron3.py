"""Least time the chip could take for one decode step of a Nemotron-H style hybrid over the time it took.

The least time is the larger of operations over peak FLOP/s and bytes over peak bytes/s for what the algorithm needs
(``counts_nemotron3.decode_step_need``): every non-expert weight once, the weights of the experts the live streams HIT (the mean
``experts_hit`` of the traced ``serve/step`` spans) and not of the 64 held, S read and written and the convolution's taps of every live
slot in every Mamba layer, K and V of the rows the attention layer reads (``attn_rows``) and writes, one float32 row of logits a live
stream. None where the program stamps no ``ssm_slots`` or ``attn_rows``.
"""
from benchmarks.harness import counts, counts_nemotron3, trace_reduce

ROWS = ("live", "attn_rows", "experts_hit")


def read(ctx):
    if ctx.trace is None or ctx.traced is None:
        return None
    step_ms = trace_reduce.executable_mean_ms(ctx, "step")
    steps = [a for n, _, _, a in ctx.spans
             if n == "serve/step" and "ssm_slots" in a and all(isinstance(a.get(k), (int, float)) for k in ROWS)]
    if step_ms is None or not steps:
        return None
    live, rows, hit = (sum(a[k] for a in steps) / len(steps) for k in ROWS)
    flops, nbytes = counts_nemotron3.decode_step_need(
        counts_nemotron3.shape(ctx.config), live, rows, hit,
        counts.dtype_bytes(ctx.config["dtype"]), counts.dtype_bytes(ctx.config["cache_dtype"]))
    t_flops = flops / (ctx.peaks["flops_per_s"] * ctx.chips)
    t_bytes = nbytes / (ctx.peaks["bytes_per_s"] * ctx.chips)
    ctx.notes["decode_roofline_bound"] = "bytes" if t_bytes >= t_flops else "flops"
    ctx.notes["decode_least_ms"] = 1e3 * max(t_flops, t_bytes)
    ctx.notes["decode_need_gbytes"] = nbytes / 1e9
    return 100.0 * max(t_flops, t_bytes) / (step_ms * 1e-3)
