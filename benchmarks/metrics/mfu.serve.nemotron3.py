"""Model operations (every non-expert matrix, 22 experts' worth routed of which an eighth is held, every Mamba layer's recurrence, attention over the true context) of every prompt whose first token, and every later token that, reached a client in the traced part, over its seconds x chips x peak FLOP/s."""
from benchmarks.harness import counts_nemotron3 as counts


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
                # token 1 comes out of the prompt pass; token k+1 out of feeding token k back at position n0 + k - 1
                flops += counts.prefill_flops(s, n0) if k == 0 else counts.decode_flops(s, n0 + k - 1)
    return 100.0 * flops / ((b - a) * ctx.chips * ctx.peaks["flops_per_s"])
