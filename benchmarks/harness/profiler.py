"""A few seconds of the profiler in the middle of the window.

``--trace 1`` runs the same traffic for the same window and traces
``trace_seconds`` of it around the middle. The trace is written under
``TMPDIR`` (the driver gives each side its own), reduced once the window
has closed, and deleted.
"""

from __future__ import annotations

import contextlib
import shutil
import tempfile
import time

from benchmarks.harness import trace_reduce

TRACE_SECONDS = 2.5


class Profiler:
    def __init__(self, trace_seconds=TRACE_SECONDS):
        self.trace_seconds = trace_seconds
        self.dir = None
        self.started_at = None
        self.stopped_at = None

    def plan(self, start, end):
        """``(a, b)``: when to start and stop, centred in the window."""
        length = min(self.trace_seconds, (end - start) / 2)
        mid = (start + end) / 2
        return mid - length / 2, mid + length / 2

    def start(self, host_tracer_level=2):
        """``host_tracer_level`` 2 (the profiler's default) keeps the
        runtime's own host events, which name the idle gaps; 0 drops them."""
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        # no Python tracer: it records every call of every thread (half a
        # million events in three seconds of serving) and slows the host it
        # is meant to observe
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = host_tracer_level
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.started_at = time.perf_counter()

    def interval_so_far(self):
        return self.started_at, time.perf_counter()

    def stop(self):
        import jax
        self.stopped_at = time.perf_counter()
        jax.profiler.stop_trace()

    def reduce(self):
        """The reduced trace, or ``None`` when nothing was traced; the
        files are deleted either way."""
        if self.dir is None:
            return None
        try:
            if self.stopped_at is None:
                self.stop()
            return trace_reduce.load_xplane(trace_reduce.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None


@contextlib.contextmanager
def compile_log():
    """``(time, function, seconds)`` of every XLA compilation, or load
    from the persistent cache, while the block runs (as
    ``chip_smoke._compile_log``). A window must hold none: a compile there
    is a shape that set-up did not warm."""
    import jax.monitoring
    log = []

    def listen(event, duration, fun_name=None, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            log.append((time.perf_counter(), fun_name, duration))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield log
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
