"""The plain token gap: mean ``gap_ms`` of the ``serve/deliver`` spans of the traced part whose block had no prefill before it (``prefills`` 0), weighted by ``gap_streams``: a decode block and the host's turn, as the loop itself measures it between two deliveries. ``notes``: the count, the weighted p95, the gap split by the loop's leaves (``serve/step.readback`` is the wait for the device, the rest the host's own work), and how late the ring's oldest span begins; None, with ``ring_overflowed``, where that is over a quarter of a second."""
from benchmarks.harness import gap_account


def read(ctx):
    late = gap_account.ring_late_s(ctx.spans, ctx.traced)
    plain = gap_account.gaps(ctx.spans, after_prefill=False)
    if late is None or not plain:
        return None
    pairs = gap_account.weights(plain)
    note = ctx.notes["token_gap_plain"] = {
        "deliveries": len(plain), "gaps": sum(g.streams for g in plain), "oldest_span_late_s": late,
        "p95_ms": gap_account.weighted_percentile(pairs, 95), "by_leaf_ms": gap_account.by_leaf_ms(ctx.spans, plain)}
    if late > gap_account.RING_LATE_S:
        note["ring_overflowed"] = True
        return None
    return gap_account.weighted_mean(pairs)
