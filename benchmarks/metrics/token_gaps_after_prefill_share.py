"""Per cent of the token gaps that held a prompt pass: ``token_gaps_after_prefill`` over ``token_gaps`` of the engine's counters at the window's end, from the engine's start (the lead-in sends the window's traffic, the warm-up a handful of requests of two tokens). Over 5, the p95 gap is a prefill gap; under 5, it is the tail of the plain gaps."""


def read(ctx):
    gaps, held = ctx.counters.get("token_gaps"), ctx.counters.get("token_gaps_after_prefill")
    if not gaps or held is None:
        return None
    return 100.0 * held / gaps
