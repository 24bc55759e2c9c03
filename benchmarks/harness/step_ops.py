"""Device time of named operations inside the launches of one executable.

A per-layer metric of a kernel or of a group of operations reads, from the
reduced trace, the operations whose name holds one of a configuration's
needles (``expert_op``, ``select_ops``, ``kernel_op``) and that START inside
a launch of the step executable, on the lowest device. A needle is HLO text
as ``trace_reduce.short_op`` shows it (and as a run's ``breakdown.device_ops``
prints it): the trace's own names carry a layout behind every shape
(``f32[36,32768]{1,0:T(8,128)}``), which is stripped before the search."""

from __future__ import annotations

import bisect

from benchmarks.harness import trace_reduce


def seconds_by_needle(red, module, needles):
    """``(seconds, launches)``: for each of ``needles`` the summed duration
    of the operations named ``*needle*`` (an operation counts for the first
    needle it holds) that start inside a launch of ``module``, and the
    number of such launches."""
    devs = red.devices()
    seconds = dict.fromkeys(needles, 0.0)
    if not devs:
        return seconds, 0
    launches = [(s, s + d) for n, s, d in red.modules.get(devs[0], ())
                if trace_reduce.module_name(n) == module]
    starts = [s for s, _ in launches]
    for n, s, d in red.ops.get(devs[0], ()):
        name = trace_reduce.short_op(n, len(n))
        needle = next((x for x in needles if x in name), None)
        if needle is not None:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < launches[i][1]:
                seconds[needle] += d
    return seconds, len(launches)
