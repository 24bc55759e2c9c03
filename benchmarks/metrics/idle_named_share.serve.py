"""Per cent of the lowest device's idle seconds between its first and last operation of the traced part that lie in gaps which a ``serve/`` leaf of the trace's host plane overlaps; ``notes`` gets the idle seconds that lie under each leaf and, for gaps under none, the runtime's event over them and its thread."""
from benchmarks.harness import span_account


def read(ctx):
    found = None if ctx.trace is None else span_account.idle_by_leaf(ctx.trace)
    if found is None:
        return None
    named_s, by_leaf, unnamed = found
    ctx.notes["idle_s_by_leaf"] = dict(sorted(by_leaf.items(), key=lambda kv: -kv[1]))
    ctx.notes["idle_s_between_leaves"] = named_s - sum(by_leaf.values())
    ctx.notes["idle_s_under_no_leaf"] = unnamed[:8]
    return 100.0 * named_s / (named_s + sum(s for _, _, s in unnamed))
