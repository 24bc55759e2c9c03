"""Least time the chip could take for one decode step over the time it took.

The least time is the larger of operations over peak FLOP/s and bytes over
peak bytes/s, for what the algorithm needs whatever implements it: every
weight once, K and V of the LIVE tokens only in the cache's dtype, one row
of logits per live stream (``counts.gpt_decode_step_need``). Live streams
and live tokens are the means over the traced part, from the clients'
token times. The note says which bound applies.
"""
from benchmarks.harness import counts, trace_reduce, window


def read(ctx):
    if ctx.trace is None or ctx.traced is None:
        return None
    step_ms = trace_reduce.executable_mean_ms(ctx, "step")
    if step_ms is None:
        return None
    a, b = ctx.traced
    slot_s, tok_s = window.live_integrals(ctx.records, (a, b))
    if slot_s <= 0:
        return None
    kw = ctx.config["constructor_kwargs"]
    flops, nbytes = counts.gpt_decode_step_need(
        slot_s / (b - a), tok_s / (b - a), kw["vocab_size"], kw["hidden_size"],
        kw["n_layers"], kw["max_position"],
        counts.dtype_bytes(ctx.config["dtype"]),
        counts.dtype_bytes(ctx.config["cache_dtype"]))
    t_flops = flops / (ctx.peaks["flops_per_s"] * ctx.chips)
    t_bytes = nbytes / (ctx.peaks["bytes_per_s"] * ctx.chips)
    ctx.notes["decode_roofline_bound"] = "bytes" if t_bytes >= t_flops else "flops"
    ctx.notes["decode_live_tokens_mean"] = tok_s / (b - a)
    return 100.0 * max(t_flops, t_bytes) / (step_ms * 1e-3)
