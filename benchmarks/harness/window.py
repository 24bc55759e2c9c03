"""Reduction of what the client side recorded to numbers over a window.

All times are ``time.perf_counter`` seconds. A window is ``(start, end)``.
Every helper takes ALL the work of the window: every request due in it,
every gap between tokens received in it, every step completed in it.
"""

from __future__ import annotations

import math


def percentile(values, q):
    """The ``q``-th percentile (0..100) by linear interpolation; ``inf``
    when any value is infinite and the rank reaches it; ``None`` when
    there is nothing to rank."""
    vals = sorted(values)
    if not vals:
        return None
    pos = (len(vals) - 1) * q / 100.0
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if math.isinf(vals[hi]):
        return math.inf
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def due_in(records, window):
    """Requests whose due time lies in the window."""
    a, b = window
    return [r for r in records if a <= r.due_at < b]


def first_token_delays(records, window):
    """Seconds from when each request of the window was DUE to its first
    token at the client; a request that failed or was refused, or never
    produced a token, counts as ``inf`` (it misses any limit)."""
    out = []
    for r in due_in(records, window):
        if r.error is not None or not r.token_at:
            out.append(math.inf)
        else:
            out.append(r.token_at[0] - r.due_at)
    return out


def token_gaps(records, window):
    """Every gap between consecutive tokens of every stream, for tokens
    received inside the window."""
    a, b = window
    out = []
    for r in records:
        t = r.token_at
        out += [t[i] - t[i - 1] for i in range(1, len(t)) if a <= t[i] < b]
    return out


def tokens_in(records, window):
    """Generated tokens delivered to clients inside the window."""
    a, b = window
    return sum(1 for r in records for t in r.token_at if a <= t < b)


def send_lateness(records, window):
    """How late the generator sent each request of the window (seconds):
    a starved generator must not read as a fast server."""
    return [r.sent_at - r.due_at for r in due_in(records, window)
            if r.sent_at is not None]


def live_integrals(records, window):
    """``(slot_seconds, token_seconds)`` over the window: the integral of
    the number of streams holding a cache row, and of the cached positions
    they hold between them, from each stream's first token (its prompt is
    then cached) to its last. Divide by the window for means."""
    a, b = window
    slot_s = tok_s = 0.0
    for r in records:
        t = r.token_at
        n0 = len(r.planned.prompt)
        for i in range(1, len(t)):
            lo, hi = max(t[i - 1], a), min(t[i], b)
            if hi > lo:
                slot_s += hi - lo
                tok_s += (hi - lo) * (n0 + i)
    return slot_s, tok_s
