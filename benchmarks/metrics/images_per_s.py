"""Images of every step completed in the window (its loss read back on the host) over the window's seconds, feed included."""


def read(ctx):
    a, b = ctx.window
    steps = sum(1 for t, _ in getattr(ctx, "steps", ()) if a <= t < b)
    return steps * ctx.counters["batch"] / (b - a) if steps else None
