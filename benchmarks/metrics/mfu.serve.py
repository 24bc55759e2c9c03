"""Model operations of every prompt (true length) whose first token, and every later token (true context) that, reached a client in the traced part, over its seconds x chips x peak FLOP/s."""
from benchmarks.harness import counts


def read(ctx):
    if ctx.trace is None or ctx.traced is None:
        return None
    a, b = ctx.traced
    kw = ctx.config["constructor_kwargs"]
    v, h, n = kw["vocab_size"], kw["hidden_size"], kw["n_layers"]
    flops = 0
    for r in ctx.records:
        n0 = len(r.planned.prompt)
        for k, t in enumerate(r.token_at):
            if a <= t < b:
                # token 1 comes out of the prompt pass; token k+1 out of feeding token k back
                flops += counts.gpt_prefill_flops(n0, v, h, n) if k == 0 \
                    else counts.gpt_decode_flops(n0 + k, v, h, n)
    return 100.0 * flops / ((b - a) * ctx.chips * ctx.peaks["flops_per_s"])
