"""The program's own spans, laid over the serving loop's steps and over the
device's idle gaps.

The program names the phases of its serving loop (``docs/observability.md``):
every span its loop thread opens carries ``iter``, the count of loop
iterations; a *parent* (``serve/step``) encloses *leaves* named
``<parent>.<phase>`` (``serve/step.dispatch``), and only leaves enter the
profiler's trace, where they lie on the device trace's clock. Two sources:

- ``ctx.spans``: ``(name, start, end, attrs)`` of every ring span that ended
  in the traced part, on ``perf_counter``;
- ``ctx.trace.host``: ``(thread, name, start, dur)`` of every host event of
  the profiler's trace of 100 us or more, the program's leaves among them.

A program without these spans (an older commit) gives every function here
nothing to read: it returns ``None`` or an empty result and does not raise.
"""

from __future__ import annotations

PROGRAM = "serve/"           # every span of the serving loop starts so


def named(spans, name):
    """The spans of ``ctx.spans`` called ``name``."""
    return [s for s in spans if s[0] == name]


def mean_ms(spans, name):
    """``(mean duration in ms, count)`` of the spans called ``name``;
    ``(None, 0)`` when there is none."""
    durs = [end - start for _, start, end, _ in named(spans, name)]
    return (1e3 * sum(durs) / len(durs) if durs else None), len(durs)


def loop_leaves(spans):
    """The leaves of the loop thread among ``ctx.spans``: spans that carry
    ``iter`` and whose name no other such span's name extends by
    ``.<phase>`` (those are the parents)."""
    on_loop = [s for s in spans if "iter" in s[3]]
    names = {s[0] for s in on_loop}
    parents = {n for n in names if any(m.startswith(n + ".") for m in names)}
    return [s for s in on_loop if s[0] not in parents]


def host_ms_per_step(spans, step="serve/step",
                     waits=("serve/idle", "serve/step.readback")):
    """``(ms, {leaf: ms})``: the summed duration of the loop thread's
    leaves other than ``waits`` (nothing to do; blocked on the device),
    over the number of ``step`` spans: the host's own work a decode step,
    in all and by leaf. ``(None, {})`` without a step or without leaves."""
    steps = len(named(spans, step))
    by_leaf = {}
    for name, start, end, _ in loop_leaves(spans):
        if name not in waits:
            by_leaf[name] = by_leaf.get(name, 0.0) + (end - start)
    if not steps or not by_leaf:
        return None, {}
    by_leaf = {n: 1e3 * s / steps for n, s in by_leaf.items()}
    return sum(by_leaf.values()), by_leaf


def useful_share(spans, name="serve/prefill"):
    """Per cent: the sum of ``tokens`` over the sum of ``rows`` x ``bucket``
    of the spans called ``name`` (prompt tokens asked for over positions
    computed); ``None`` when no such span carries all three."""
    asked = computed = 0
    for _, _, _, a in named(spans, name):
        if all(isinstance(a.get(k), (int, float))
               for k in ("tokens", "rows", "bucket")):
            asked += a["tokens"]
            computed += a["rows"] * a["bucket"]
    return 100.0 * asked / computed if computed else None


def device_gaps(red, min_gap_s=5e-5):
    """``[(start, end), ...]``: the intervals between the first and the last
    device event in which no operation ran on the lowest device (as
    ``trace_reduce.idle_gaps`` cuts them), on the trace's clock."""
    devs = red.devices()
    if not devs:
        return []
    evs = red.ops.get(devs[0]) or red.modules.get(devs[0]) or []
    gaps, end = [], None
    for _, s, d in evs:
        if end is not None and s - end >= min_gap_s:
            gaps.append((end, s))
        end = s + d if end is None else max(end, s + d)
    return gaps


def _longest_over(events, a, b):
    """The event of ``events`` (``(thread, name, start, dur)``) that covers
    most of ``[a, b]``, the earlier one where two cover the same; ``None``
    when none overlaps it."""
    best, cover = None, 0.0
    for e in events:
        c = min(b, e[2] + e[3]) - max(a, e[2])
        if c > cover:
            best, cover = e, c
    return best


def idle_by_leaf(red, prefix=PROGRAM):
    """``(named_s, by_leaf, unnamed)`` or ``None``, over the idle gaps of the
    lowest device. ``named_s``: the seconds of the gaps that some leaf of
    the program (a host event whose name starts with ``prefix``) overlaps.
    ``by_leaf``: ``{leaf: seconds}``, the part of those gaps that lies under
    each leaf (leaves of one thread do not overlap; what lies between two
    of them, or under a leaf too short for the trace to keep, is the rest
    of ``named_s``). ``unnamed``: ``[[thread, event, seconds], ...]``, the
    gaps no leaf overlaps, by the longest other host event over them
    (``None`` where there is none either), largest first. ``None`` where
    the trace holds no gap or no host event of the program at all."""
    gaps = device_gaps(red)
    leaves = [e for e in red.host if e[1].startswith(prefix)]
    if not gaps or not leaves:
        return None
    others = [e for e in red.host if not e[1].startswith(prefix)]
    named_s, by_leaf, unnamed = 0.0, {}, {}
    for a, b in gaps:
        under = [(e[1], min(b, e[2] + e[3]) - max(a, e[2])) for e in leaves]
        under = [(n, c) for n, c in under if c > 0]
        if under:
            named_s += b - a
            for n, c in under:
                by_leaf[n] = by_leaf.get(n, 0.0) + c
        else:
            other = _longest_over(others, a, b)
            key = (None, None) if other is None else (other[0], other[1])
            unnamed[key] = unnamed.get(key, 0.0) + (b - a)
    return named_s, by_leaf, [[t, n, s] for (t, n), s in
                              sorted(unnamed.items(), key=lambda kv: -kv[1])]


def client_first_token_ms(records, traced):
    """``(mean ms, count)`` of first token at the client minus the due time,
    over the requests that were due inside ``traced`` and got a token: the
    client's side of what ``serve/queue_wait`` + ``serve/first_token``
    split. ``(None, 0)`` when there is none."""
    if traced is None:
        return None, 0
    a, b = traced
    delays = [r.token_at[0] - r.due_at for r in records
              if a <= r.due_at < b and r.error is None and r.token_at]
    return (1e3 * sum(delays) / len(delays) if delays else None), len(delays)
