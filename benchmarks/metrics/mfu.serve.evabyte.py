"""Model operations (the seven matrices a layer and the whole head a byte; attention over the rows a position really reads: its window's exact rows and the summaries of closed windows) of every prompt whose first byte, and every later byte that, reached a client in the traced part, over its seconds x chips x peak FLOP/s."""
from benchmarks.harness import counts_evabyte as counts


def read(ctx):
    if ctx.trace is None or ctx.traced is None:
        return None
    a, b = ctx.traced
    s = counts.shape(ctx.config)
    flops = 0
    for r in ctx.records:
        n0 = len(r.planned.prompt)
        for k, t in enumerate(r.token_at):
            if a <= t < b:
                # byte 1 comes out of the prompt pass; byte k+1 out of feeding byte k back at position n0 + k - 1
                flops += counts.prefill_flops(s, n0) if k == 0 else counts.decode_flops(s, n0 + k - 1)
    if not flops:       # no token reached a client in the traced part
        return None
    return 100.0 * flops / ((b - a) * ctx.chips * ctx.peaks["flops_per_s"])
