"""The token gap, as the serving loop measures it on its ``serve/deliver``
spans, reduced for the four metrics that read it.

Since PR 36 the program stamps on every ``serve/deliver`` (``ctx.spans``:
``(name, start, end, attrs)`` on ``perf_counter``) the numbers of the gap
that the delivery ends (``docs/observability.md``): ``gap_ms``, the time
since the delivery before, and ``gap_streams``, the streams that got a
token in both (the weight of everything here); ``prefills`` and
``prefill_positions``, the prefill executables the device had queued before
the delivered block, and the positions they computed. A gap with
``prefills`` 0 is *plain* (a decode block and the host's turn), one with
``prefills`` > 0 holds a prompt pass as well. ``engine.stats`` keeps the
sums ``token_gaps`` and ``token_gaps_after_prefill`` over the whole run
(``ctx.counters``).

A program without these attributes (an older commit) gives every function
here nothing to read: it returns ``None`` or an empty result and does not
raise.
"""

from __future__ import annotations

import bisect
import collections

from benchmarks.harness import span_account

DELIVER = "serve/deliver"
RING_LATE_S = 0.25                # the oldest span may begin so late

# one delivery's gap: ``ms`` and its weight ``streams``, the prefills before
# its block and their ``positions``, and the delivery's ``start``
Gap = collections.namedtuple("Gap", "ms streams prefills positions start")


def gaps(spans, after_prefill):
    """The :class:`Gap` of every delivery that ends a gap of the asked
    class (``after_prefill`` False: ``prefills`` 0; True: ``prefills`` >
    0), in the order of ``spans``."""
    out = []
    for _, start, _, a in span_account.named(spans, DELIVER):
        gap, n, pre = a.get("gap_ms"), a.get("gap_streams"), a.get("prefills")
        if not all(isinstance(v, (int, float)) for v in (gap, n, pre)):
            continue
        if n > 0 and (pre > 0) == bool(after_prefill):
            out.append(Gap(float(gap), int(n), int(pre),
                           int(a.get("prefill_positions") or 0), start))
    return out


def weights(gaps_of_class):
    """``[(ms, streams), ...]``: what the weighted reductions take."""
    return [(g.ms, g.streams) for g in gaps_of_class]


def weighted_mean(pairs):
    """The mean of ``value`` weighted by ``weight`` over ``(value, weight)``
    pairs; ``None`` without weight."""
    pairs = list(pairs)
    weight = sum(w for _, w in pairs)
    return sum(v * w for v, w in pairs) / weight if weight else None


def weighted_percentile(pairs, q):
    """The smallest ``value`` at or under which ``q`` per cent of the
    weight of ``(value, weight)`` pairs lies; ``None`` without weight."""
    pairs = sorted(pairs)
    weight = sum(w for _, w in pairs)
    if not weight:
        return None
    acc = 0.0
    for v, w in pairs:
        acc += w
        if acc >= weight * q / 100.0:
            return v
    return pairs[-1][0]


def ring_late_s(spans, traced):
    """How long after the traced part's start the oldest of the loop's
    spans in ``spans`` begins (those with ``iter``: a request's wait in the
    queue is recorded when it ends and may have begun long before): the
    program's ring holds a fixed number of spans, and where a slice
    outgrows it the slice's first spans are gone without a word. ``None``
    without such spans or without a traced part."""
    starts = [s[1] for s in spans if "iter" in s[3]]
    if not starts or traced is None:
        return None
    return min(starts) - traced[0]


def by_leaf_ms(spans, gaps_of_class):
    """``{leaf: ms}``: the mean gap of ``gaps_of_class`` split by the
    loop's leaves that tile it (each leaf's overlap with ``[start - gap,
    start]``, weighted as the gaps are). ``serve/step.readback`` is the
    wait for the device, the others the host's own work; what no leaf
    covers is the loop's own lines between them."""
    leaves = sorted(span_account.loop_leaves(spans), key=lambda s: s[1])
    ends = [s[2] for s in leaves]
    total, weight = {}, 0
    for g in gaps_of_class:
        a, b, n = g.start - g.ms / 1e3, g.start, g.streams
        weight += n
        # leaves of one thread do not overlap, so their ends are sorted too
        for name, s, e, _ in leaves[bisect.bisect_right(ends, a):]:
            if s >= b:
                break
            total[name] = total.get(name, 0.0) + n * (min(e, b) - max(s, a))
    if not weight:
        return {}
    return {name: 1e3 * v / weight
            for name, v in sorted(total.items(), key=lambda kv: -kv[1])}


def client_lags(spans, records, traced):
    """Seconds from the start of the latest ``serve/deliver`` at or before
    it to each token after its stream's first that a client received in
    the traced part (``Record.token_at``, the same clock): the fan-out
    through ``Request._deliver`` and the caller's wake-up. Empty without
    deliveries that carry the gap's attributes."""
    starts = sorted(s[1] for s in span_account.named(spans, DELIVER)
                    if "prefills" in s[3])
    if not starts or traced is None:
        return []
    a, b = traced
    out = []
    for r in records:
        for t in r.token_at[1:]:
            if a <= t <= b:
                i = bisect.bisect_right(starts, t)
                if i:
                    out.append(t - starts[i - 1])
    return out
