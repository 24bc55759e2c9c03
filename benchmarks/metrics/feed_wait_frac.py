"""Share of the loop's host time inside the window that it spent waiting for the feed: the program's own ``data_time`` over ``data_time + step_time``."""


def read(ctx):
    feed, step = ctx.counters.get("window_data_time"), ctx.counters.get("window_step_time")
    if feed is None or step is None or feed + step <= 0:
        return None
    return 100.0 * feed / (feed + step)
