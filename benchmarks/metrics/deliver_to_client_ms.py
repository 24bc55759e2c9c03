"""From the loop's delivery to the client: mean, over the tokens after the first that clients received in the traced part, of ``token_at`` less the start of the latest ``serve/deliver`` at or before it: the fan-out through ``Request._deliver`` and the caller's wake-up under the interpreter lock. ``notes``: the count, the p95, and the share of tokens whose lag exceeds half a plain gap (there the match to a delivery may be one off)."""
from benchmarks.harness import gap_account, window


def read(ctx):
    lags = gap_account.client_lags(ctx.spans, ctx.records, ctx.traced)
    if not lags:
        return None
    note = ctx.notes["deliver_to_client"] = {"tokens": len(lags), "p95_ms": 1e3 * window.percentile(lags, 95)}
    plain = gap_account.weighted_mean(gap_account.weights(gap_account.gaps(ctx.spans, after_prefill=False)))
    if plain:
        note["over_half_a_plain_gap_share"] = 100.0 * sum(1 for v in lags if 1e3 * v > plain / 2) / len(lags)
    return 1e3 * sum(lags) / len(lags)
