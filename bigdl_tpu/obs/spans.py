"""Host-side span tracer: nested, thread-aware, Perfetto-exportable.

Dapper-style wall-time spans for the *host orchestration* around the
fused XLA programs — feed, dispatch, drain/readback, allreduce sync,
validation, checkpoint, serving prefill/step/delivery. That is where the
honest wall time lives: a jitted step is ONE device program, and the
per-phase breakdown the reference got from Spark accumulators
(``optim/Metrics.scala``) exists TPU-natively only on the host side of
each dispatch. Device-internal truth stays with ``utils.profiling.trace``
(the xplane profiler); these spans are its cheap always-on complement.

Spans must NEVER be opened inside jit-traced code: under trace they
would run once at trace time (timing the *compile*, not the step) and
their registry/ring-buffer mutations would leak host work into the hot
trace. The ``span-in-jit`` jaxlint rule enforces this statically.

Usage::

    from bigdl_tpu import obs

    with obs.span("train/dispatch", step=n):
        step_fn(...)                     # timed host section

    obs.record_span("train/feed", t_data, t0, step=n)   # after the fact

    with obs.leaf_span("serve/step.dispatch", iter=n):  # also in the
        step_fn(...)                                    # profiler's trace

Two sinks, one call site. Every span lands in the ring; a *leaf* span
(:func:`leaf_span`: a phase that encloses no other span and ends within
the loop iteration that opened it) ALSO enters the tracer's *annotator*,
a factory ``(name, attrs) -> context manager`` that ``obs`` never builds
itself (it stays stdlib-only): ``utils.profiling.install_trace_annotator``
installs ``jax.profiler.TraceAnnotation``, so that while a profiler
session runs the leaf lies on its thread's line of the ``/host:CPU``
plane, on the device trace's clock, and a device idle gap can be named by
the phase the host was in. Only leaves: a tool that names a gap by the
longest host event over it would give every gap to an enclosing span.

Spans land in a bounded ring buffer (old spans fall off; a soak can run
forever at O(capacity) memory) and export as Chrome trace-event JSON —
``chrome://tracing`` / https://ui.perfetto.dev load it directly, with
per-thread tracks and nesting rendered from the timestamps. Nesting is
also recorded explicitly (``parent``/``depth`` per span, tracked
per-thread), so tests and text tooling need no interval math.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque

from bigdl_tpu.obs import metrics as _metrics
from bigdl_tpu.utils.engine import get_flag


class Span:
    """One closed span: name, [start, end) in tracer-epoch seconds,
    originating thread, explicit nesting, free-form attrs."""

    __slots__ = ("name", "start", "end", "thread_id", "thread_name",
                 "parent", "depth", "attrs")

    def __init__(self, name, start, end, thread_id, thread_name,
                 parent=None, depth=0, attrs=None):
        self.name = name
        self.start = start
        self.end = end
        self.thread_id = thread_id
        self.thread_name = thread_name
        self.parent = parent
        self.depth = depth
        self.attrs = attrs or {}

    @property
    def duration(self):
        return self.end - self.start

    def __repr__(self):
        return (f"Span({self.name!r}, {self.duration * 1e3:.3f} ms, "
                f"thread={self.thread_name!r}, depth={self.depth})")


class _SpanContext:
    """Class-based context manager for :meth:`SpanTracer.span` — a
    generator ``@contextmanager`` costs several microseconds per use in
    interpreter machinery alone, which matters for a per-step probe.
    The enabled check happens at ``__enter__`` (not construction) so a
    pre-built context still respects a later kill-switch flip; it is the
    one check that silences both sinks.

    The clock is read on entry and exit whatever the kill switch says
    (``start``/``end`` in tracer-epoch seconds, :attr:`duration` once
    closed), so a caller that needs the interval itself (the scheduler's
    ``step_seconds``) takes it from the span instead of timing the same
    section a second time."""

    __slots__ = ("_tracer", "_name", "_attrs", "_leaf", "_on", "_ann",
                 "start", "end")

    def __init__(self, tracer, name, attrs, leaf=False):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs
        self._leaf = leaf
        self._on = False
        self._ann = None
        self.start = self.end = 0.0

    @property
    def duration(self):
        return self.end - self.start

    def set(self, **attrs):
        """Attributes known only once the section has run (a batch size,
        a token count). They reach the ring; the profiler's event keeps
        the attributes the span was opened with."""
        self._attrs.update(attrs)

    def __enter__(self):
        tracer = self._tracer
        self._on = _metrics._enabled
        if self._on:
            if self._leaf and tracer.annotator is not None:
                self._ann = tracer.annotator(self._name, self._attrs)
                self._ann.__enter__()
            stack = getattr(tracer._local, "stack", None)
            if stack is None:
                stack = tracer._local.stack = []
            stack.append(self._name)
        self.start = time.perf_counter() - tracer.epoch_perf
        return self

    def __exit__(self, exc_type, exc, tb):
        tracer = self._tracer
        self.end = time.perf_counter() - tracer.epoch_perf
        if not self._on:  # was disabled at __enter__
            return False
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(exc_type, exc, tb)
        stack = tracer._local.stack
        stack.pop()
        tracer._append(self._name, self.start, self.end,
                       parent=stack[-1] if stack else None,
                       depth=len(stack), attrs=self._attrs)
        return False


class SpanTracer:
    """Bounded ring buffer of :class:`Span`, with per-thread nesting
    stacks. All methods are thread-safe; recording is a clock read plus
    one locked deque append."""

    def __init__(self, capacity=None, annotator=None):
        if capacity is None:
            capacity = get_flag("BIGDL_TPU_OBS_SPAN_CAPACITY", 8192, int)
        # the second sink of a leaf span (module docstring): a factory
        # ``(name, attrs) -> context manager``, or None for the ring alone
        self.annotator = annotator
        self._lock = threading.Lock()
        self._buf = deque(maxlen=max(1, int(capacity)))
        self._local = threading.local()
        # epoch: perf_counter is monotonic but arbitrary-origin; anchor it
        # to wall time once so exported timestamps are interpretable
        self.epoch_perf = time.perf_counter()
        self.epoch_wall = time.time()

    # --------------------------------------------------------- recording --
    def span(self, name, **attrs):
        """Time a host section. Nesting is per-thread: a span opened while
        another is open on the same thread records it as its parent."""
        return _SpanContext(self, name, attrs)

    def leaf_span(self, name, **attrs):
        """:meth:`span` for a leaf phase: ring AND the annotator (module
        docstring). Never for a span that encloses other spans, nor for
        one that outlives the loop iteration that opened it."""
        return _SpanContext(self, name, attrs, leaf=True)

    def record(self, name, start, end, **attrs):
        """Record an already-timed section (``time.time()`` or
        ``perf_counter`` values both work — anything monotonic enough
        that ``end - start`` is the duration), placed so that it ends
        now. For instrumenting existing timed code without restructuring
        it; records at the current thread's nesting depth. Ring only: the
        section is over, so there is nothing for the annotator to enter."""
        if not _metrics._enabled:
            return
        dur = max(0.0, end - start)
        now = time.perf_counter() - self.epoch_perf
        stack = getattr(self._local, "stack", None) or []
        self._append(name, now - dur, now,
                     parent=stack[-1] if stack else None,
                     depth=len(stack), attrs=attrs)

    def record_at(self, name, start, end, **attrs):
        """Record the interval between two ``perf_counter`` readings,
        placed where they were taken: for one that several threads or
        loop iterations bound (a request's wait in the queue), so it has
        no parent, and its ends meet those of its neighbours exactly.
        Ring only."""
        if not _metrics._enabled:
            return
        self._append(name, start - self.epoch_perf, end - self.epoch_perf,
                     parent=None, depth=0, attrs=attrs)

    def _append(self, name, start, end, parent, depth, attrs):
        t = threading.current_thread()
        s = Span(name, start, end, t.ident, t.name,
                 parent=parent, depth=depth, attrs=attrs)
        # lock-free: deque.append is atomic under the GIL, and this is
        # the per-step hot path.  The lock below only serializes reads
        # and capacity swaps against each other; an append racing
        # set_capacity can at worst land on the retiring deque (one
        # dropped span), which a resize is allowed to do anyway.
        self._buf.append(s)

    # ------------------------------------------------------------- reads --
    def spans(self):
        """Snapshot of the ring buffer, oldest first."""
        with self._lock:
            return list(self._buf)

    def __len__(self):
        with self._lock:
            return len(self._buf)

    @property
    def capacity(self):
        return self._buf.maxlen

    def clear(self):
        with self._lock:
            self._buf.clear()

    def set_capacity(self, capacity):
        """Resize the ring (keeps the newest spans that fit)."""
        with self._lock:
            self._buf = deque(self._buf, maxlen=max(1, int(capacity)))

    # ------------------------------------------------------------ export --
    def chrome_trace(self):
        """Chrome trace-event JSON (the ``/trace`` page content): complete
        ("ph":"X") events in microseconds, one track per thread, plus
        metadata ("M") events — ``thread_name`` so Perfetto shows the
        copier/scheduler/writer thread names instead of bare tids
        (covering live ``bigdl-tpu-*`` worker threads even before their
        first span lands), ``thread_sort_index`` pinning a stable
        name-sorted track order across exports, and ``process_name`` —
        drop the dict into https://ui.perfetto.dev or chrome://tracing
        as-is."""
        pid = os.getpid()
        events, threads = [], {}
        for s in self.spans():
            threads.setdefault(s.thread_id, s.thread_name)
            args = dict(s.attrs)
            if s.parent is not None:
                args["parent"] = s.parent
            events.append({
                "name": s.name, "cat": "host", "ph": "X",
                "ts": s.start * 1e6, "dur": s.duration * 1e6,
                "pid": pid, "tid": s.thread_id, "args": args,
            })
        # name every live bigdl-tpu worker thread too: a copier or
        # snapshot writer that has not recorded a span yet still gets a
        # labeled (empty) track instead of appearing later as a bare tid
        for t in threading.enumerate():
            if t.ident is not None and t.name.startswith("bigdl-tpu-"):
                threads.setdefault(t.ident, t.name)
        meta = [{"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                 "args": {"name": tname}}
                for tid, tname in sorted(threads.items())]
        order = sorted(threads.items(), key=lambda kv: (kv[1], kv[0]))
        meta.extend({"name": "thread_sort_index", "ph": "M", "pid": pid,
                     "tid": tid, "args": {"sort_index": idx}}
                    for idx, (tid, _) in enumerate(order))
        meta.append({"name": "process_name", "ph": "M", "pid": pid,
                     "args": {"name": "bigdl_tpu host"}})
        return {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": {"epoch_unix_s": self.epoch_wall,
                          "producer": "bigdl_tpu.obs"},
        }

    def export(self, path):
        """Write :meth:`chrome_trace` to ``path`` (Perfetto-loadable)."""
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path


# ------------------------------------------------------------ default tracer
_default = SpanTracer()


def default_tracer():
    """The process-global tracer every built-in span lands in."""
    return _default


def span(name, **attrs):
    """``with obs.span("train/dispatch", step=n): ...`` on the default
    tracer. Host orchestration only — never inside jit-traced code."""
    return _default.span(name, **attrs)


def leaf_span(name, **attrs):
    """:func:`span` for a leaf phase on the default tracer: it also
    enters the profiler's trace (:meth:`SpanTracer.leaf_span`)."""
    return _default.leaf_span(name, **attrs)


def record_span(name, start, end, **attrs):
    """Record an already-timed section on the default tracer (ring only:
    it never reaches the profiler's trace)."""
    _default.record(name, start, end, **attrs)


def record_span_at(name, start, end, **attrs):
    """Record the interval between two ``perf_counter`` readings on the
    default tracer, placed where they were taken (ring only)."""
    _default.record_at(name, start, end, **attrs)
