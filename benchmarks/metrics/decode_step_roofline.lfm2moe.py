"""Least time the chip could take for one decode step of an LFM2-MoE style model over the time it took.

The least time is the larger of operations over peak FLOP/s and bytes over
peak bytes/s for what the algorithm needs (``counts_lfm2moe.decode_step_need``):
every non-expert weight once, the weights of the experts the live slots HIT
(the mean ``experts_hit`` of the traced ``serve/step`` spans) and not of all,
K and V of the live tokens in the attention layers, the live slots'
convolution states, one row of logits a live slot. Live streams and tokens
are the means over the traced part, from the clients' token times. None
where the program stamps no ``experts_hit``.
"""
from benchmarks.harness import counts, counts_lfm2moe, trace_reduce, window


def read(ctx):
    if ctx.trace is None or ctx.traced is None:
        return None
    step_ms = trace_reduce.executable_mean_ms(ctx, "step")
    hit = [a["experts_hit"] for n, _, _, a in ctx.spans
           if n == "serve/step" and isinstance(a.get("experts_hit"), (int, float))]
    if step_ms is None or not hit:
        return None
    a, b = ctx.traced
    slot_s, tok_s = window.live_integrals(ctx.records, (a, b))
    if slot_s <= 0:
        return None
    flops, nbytes = counts_lfm2moe.decode_step_need(
        counts_lfm2moe.shape(ctx.config), slot_s / (b - a), tok_s / (b - a),
        sum(hit) / len(hit), counts.dtype_bytes(ctx.config["dtype"]),
        counts.dtype_bytes(ctx.config["cache_dtype"]))
    t_flops = flops / (ctx.peaks["flops_per_s"] * ctx.chips)
    t_bytes = nbytes / (ctx.peaks["bytes_per_s"] * ctx.chips)
    ctx.notes["decode_roofline_bound"] = "bytes" if t_bytes >= t_flops else "flops"
    ctx.notes["decode_live_tokens_mean"] = tok_s / (b - a)
    ctx.notes["decode_least_ms"] = 1e3 * max(t_flops, t_bytes)
    return 100.0 * max(t_flops, t_bytes) / (step_ms * 1e-3)
