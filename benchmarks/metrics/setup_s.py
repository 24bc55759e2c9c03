"""Process start to the window's start: build, weights, warm-up of the cell's own shapes, compile or cache load, and the traffic's lead-in."""


def read(ctx):
    return ctx.until_window_s
