"""Compilations (or loads from the persistent cache) inside the window: a shape that set-up did not warm compiles here instead."""


def read(ctx):
    return ctx.counters.get("compiles_in_window")
