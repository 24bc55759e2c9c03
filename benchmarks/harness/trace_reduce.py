"""From the profiler's trace to intervals, and from intervals to numbers.

Two stages, so that the arithmetic can be checked on a small recorded
trace without a chip: :func:`load_xplane` turns an ``.xplane.pb`` into a
:class:`Reduced` (plain lists of ``(name, start_s, duration_s)``), and the
functions below work on a ``Reduced`` alone.

Plane and line names are the TPU profiler's (jax 0.9, libtpu 0.0.34):
one plane per chip, ``/device:TPU:<n>``, with a line ``XLA Modules`` (one
event per executable launch, named ``<jit name>(<fingerprint>)``) and a
line ``XLA Ops`` (one event per HLO operation); host threads are lines of
the plane ``/host:CPU``.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
               "collective-permute")


class Reduced:
    """``ops[dev]`` and ``modules[dev]``: lists of ``(name, start, dur)``
    in seconds on the trace's clock, sorted by start; ``host``: list of
    ``(thread, name, start, dur)``; ``span``: ``(first, last)`` second
    any device event was seen."""

    def __init__(self, ops, modules, host):
        self.ops = {int(k): sorted(v, key=lambda e: e[1])
                    for k, v in ops.items()}
        self.modules = {int(k): sorted(v, key=lambda e: e[1])
                        for k, v in modules.items()}
        self.host = sorted(host, key=lambda e: e[2])

    @classmethod
    def from_json(cls, path):
        with open(path) as f:
            d = json.load(f)
        return cls(d["ops"], d["modules"], [tuple(h) for h in d["host"]])

    def devices(self):
        return sorted(set(self.ops) | set(self.modules))


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path, host_min_s=1e-4):
    """Read an ``.xplane.pb`` with JAX's own reader. Host events shorter
    than ``host_min_s`` are dropped (they label no gap worth naming)."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops[dev] = [(e.name, e.start_ns * 1e-9,
                                 e.duration_ns * 1e-9) for e in line.events]
                elif line.name == MODULE_LINE:
                    modules[dev] = [(e.name, e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9)
                                    for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns * 1e-9 >= host_min_s:
                        host.append((line.name, e.name, e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9))
    return Reduced(ops, modules, host)


# --------------------------------------------------------------- numbers --
def module_name(event_name):
    """``jit_step(123456)`` -> ``jit_step``."""
    return event_name.split("(", 1)[0]


def union_seconds(intervals):
    """Total length of the union of ``(start, dur)`` intervals."""
    total, end = 0.0, None
    for start, dur in sorted(intervals):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def trace_span(red):
    """``(first, last)`` second of any device event."""
    firsts, lasts = [], []
    for table in (red.ops, red.modules):
        for evs in table.values():
            if evs:
                firsts.append(evs[0][1])
                lasts.append(max(s + d for _, s, d in evs))
    if not firsts:
        return None
    return min(firsts), max(lasts)


def busy_seconds(red, dev):
    """Seconds in which an operation ran on ``dev`` (union of its op
    intervals; of its module intervals where the trace has no op line)."""
    evs = red.ops.get(dev) or red.modules.get(dev) or []
    return union_seconds([(s, d) for _, s, d in evs])


def mean_busy_seconds(red):
    devs = red.devices()
    if not devs:
        return None
    return sum(busy_seconds(red, d) for d in devs) / len(devs)


def module_durations(red, name, dev=None):
    """Device durations (seconds) of every launch of the executable whose
    module name is ``name``, on ``dev`` (default: the lowest device)."""
    devs = red.devices()
    if not devs:
        return []
    dev = devs[0] if dev is None else dev
    return [d for n, _, d in red.modules.get(dev, ())
            if module_name(n) == name]


def module_mean_ms(red, name):
    """Mean device milliseconds of a launch of the executable ``name``;
    ``None`` when it never ran in the trace."""
    d = module_durations(red, name)
    return 1e3 * sum(d) / len(d) if d else None


def executable_mean_ms(ctx, which):
    """Mean device milliseconds of a launch, in the traced part, of the
    executable that the configuration's ``executables`` names ``which``;
    ``None`` without a trace or when it never ran."""
    if ctx.trace is None:
        return None
    return module_mean_ms(ctx.trace, ctx.config["executables"][which])


def busy_and_window(red, traced=None):
    """``(busy_s, window_s)``: seconds an operation ran, averaged over the
    chips, and the length of the traced part: the host's interval
    ``traced`` (start, end) from the profiler's start to its stop, so that
    idle time before the first device event and after the last counts;
    never shorter than the span the device events themselves cover.
    ``None`` for an empty trace."""
    span = trace_span(red)
    busy = mean_busy_seconds(red)
    if span is None or not busy:
        return None
    length = span[1] - span[0]
    if traced is not None:
        length = max(length, traced[1] - traced[0])
    return busy, length


def idle_share(red, traced=None):
    """Per cent of the traced part in which no operation ran on the
    device, averaged over the chips; ``None`` for an empty trace."""
    both = busy_and_window(red, traced)
    return None if both is None else 100.0 * (1.0 - both[0] / both[1])


def module_names(red):
    """Module name -> ``(launches, seconds)`` on the lowest device."""
    out = {}
    devs = red.devices()
    for n, _, d in red.modules.get(devs[0], ()) if devs else ():
        e = out.setdefault(module_name(n), [0, 0.0])
        e[0] += 1
        e[1] += d
    return out


def op_seconds(red, dev, predicate):
    """Device seconds of the operations on ``dev`` whose name satisfies
    ``predicate`` (union, so nested or overlapping events count once)."""
    return union_seconds([(s, d) for n, s, d in red.ops.get(dev, ())
                          if predicate(n)])


def is_collective(op_name):
    base = op_name.split(".", 1)[0].split("(", 1)[0].lstrip("%")
    return any(base.startswith(c) for c in COLLECTIVES)


def collective_share(red, step_module):
    """Device time of collective operations over the device time of the
    ``step_module`` executable's launches, on the device where that share
    is largest; ``None`` when the module never ran."""
    best = None
    for dev in red.devices():
        step = sum(module_durations(red, step_module, dev))
        if step <= 0:
            continue
        share = op_seconds(red, dev, is_collective) / step
        best = share if best is None else max(best, share)
    return best


def short_op(name, width=96):
    """``%fusion.3 = f32[8,128]{...} fusion(...)`` -> ``%fusion.3 =
    f32[8,128] fusion(``: the HLO text without layouts, cut to
    ``width``."""
    return re.sub(r"\{[^{}]*\}", "", name)[:width]


def top_ops(red, k=10):
    """The ``k`` device operations that took most time on the lowest
    device: ``[[name, seconds], ...]``. A ``while`` or ``conditional``
    counts with everything that ran inside it."""
    devs = red.devices()
    if not devs:
        return []
    acc = {}
    for n, _, d in red.ops.get(devs[0], ()):
        acc[n] = acc.get(n, 0.0) + d
    # operations that differ only in their numbering (one cache write a
    # layer, say) are one entry, with how many there were
    kinds = {}
    for n, s in acc.items():
        e = kinds.setdefault(re.sub(r"\.\d+", "", short_op(n, 10 ** 6)), [0, 0.0])
        e[0] += 1
        e[1] += s
    return [[(f"{c}x " if c > 1 else "") + n[:96], s] for n, (c, s)
            in sorted(kinds.items(), key=lambda kv: -kv[1][1])[:k]]


def idle_gaps(red, k=10, min_gap_s=5e-5):
    """The idle time of the lowest device, grouped by what came next and
    by what the host was doing: ``[[label, seconds], ...]``, largest
    first. A gap is labelled with the executable launched after it and
    the longest profiler host event that overlaps it."""
    devs = red.devices()
    if not devs:
        return []
    dev = devs[0]
    mods = red.modules.get(dev) or []
    evs = red.ops.get(dev) or mods
    gaps, end = [], None
    for _, s, d in evs:
        if end is not None and s - end >= min_gap_s:
            gaps.append((end, s))
        end = s + d if end is None else max(end, s + d)
    acc = {}
    hi, host = 0, red.host
    starts = [s for _, s, _ in mods]
    for a, b in gaps:
        i = bisect.bisect_left(starts, b - 1e-6)
        if i > 0 and mods[i - 1][1] + mods[i - 1][2] > b:
            where = "inside " + module_name(mods[i - 1][0])
        elif i < len(mods):
            where = "before " + module_name(mods[i][0])
        else:
            where = "at the end of the trace"
        while hi < len(host) and host[hi][2] + host[hi][3] < a:
            hi += 1
        best, cover = None, 0.0
        j = hi
        while j < len(host) and host[j][2] < b:
            c = min(b, host[j][2] + host[j][3]) - max(a, host[j][2])
            if c > cover:
                best, cover = host[j][1], c
            j += 1
        label = where + (f" | host: {best[:60]}" if best else "")
        acc[label] = acc.get(label, 0.0) + (b - a)
    return [[n, s] for n, s in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]
