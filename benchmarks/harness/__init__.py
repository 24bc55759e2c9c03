"""The benchmark's yardstick: traffic, reduction, counts and peaks, and the
two helpers every runner needs. Names no cell, configuration or metric."""

import importlib
import time


def resolve(path):
    """The object a configuration names by dotted path: a module's
    attribute, or an attribute of one (``pkg.mod.Class.method``)."""
    parts = path.split(".")
    for cut in range(len(parts) - 1, 0, -1):
        try:
            obj = importlib.import_module(".".join(parts[:cut]))
        except ImportError:
            continue
        for name in parts[cut:]:
            obj = getattr(obj, name)
        return obj
    raise ImportError(path)


def sleep_until(t):
    """Sleep until ``time.perf_counter()`` reaches ``t``."""
    while True:
        wait = t - time.perf_counter()
        if wait <= 0:
            return
        time.sleep(min(wait, 0.25))
