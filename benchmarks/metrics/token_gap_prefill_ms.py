"""The token gap that holds a prompt pass: mean ``gap_ms`` of the ``serve/deliver`` spans of the traced part whose block had a prefill queued before it on the device (``prefills`` > 0), weighted by ``gap_streams``. ``notes``: the count, the mean by number of prefills, the mean ``prefill_positions``."""
from benchmarks.harness import gap_account


def read(ctx):
    held = gap_account.gaps(ctx.spans, after_prefill=True)
    if not held:
        return None
    by_count = {}
    for g in held:
        by_count.setdefault(g.prefills, []).append((g.ms, g.streams))
    ctx.notes["token_gap_prefill"] = {
        "deliveries": len(held), "gaps": sum(g.streams for g in held),
        "prefill_positions_mean": gap_account.weighted_mean((g.positions, g.streams) for g in held),
        "by_prefills": {str(k): {"deliveries": len(v), "ms": gap_account.weighted_mean(v)} for k, v in sorted(by_count.items())}}
    return gap_account.weighted_mean(gap_account.weights(held))
