"""The host's own work a decode step: summed duration of the loop thread's leaves other than ``serve/idle`` and ``serve/step.readback``, over the number of ``serve/step`` spans, in the traced part. The loop reads a step's tokens back before it dispatches again, so nearly all of it is time the chip waits."""
from benchmarks.harness import span_account


def read(ctx):
    ms, by_leaf = span_account.host_ms_per_step(ctx.spans)
    if by_leaf:
        ctx.notes["step_host_ms_by_leaf"] = dict(sorted(by_leaf.items(), key=lambda kv: -kv[1]))
    return ms
