"""95th percentile, over every request due in the window, of first token at the client minus the time the request was DUE (open loop); a failed or refused request misses."""
import math

from benchmarks.harness import window

MISSED_MS = 3.6e6     # a request that never answered: an hour, not infinity, so the line stays JSON


def read(ctx):
    v = window.percentile(window.first_token_delays(ctx.records, ctx.window), 95)
    if v is None:
        return None
    return MISSED_MS if math.isinf(v) else v * 1e3
