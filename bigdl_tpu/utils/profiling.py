"""Per-layer timing + device tracing.

Reference: ``nn/abstractnn/AbstractModule.scala:240-266`` wraps every
``updateOutput``/``updateGradInput`` in nanoTime and exposes
``getTimes``/``resetTimes``; containers aggregate children
(``nn/Container.scala``). The straggler threshold and perf debugging both
feed off it.

TPU-natively a jitted train step is ONE fused XLA program — per-layer wall
time inside it does not exist. So this module provides the two honest
equivalents:

- :func:`per_layer_times` — drive a model layer-by-layer *eagerly* (each
  layer jit-compiled separately, synchronised with ``block_until_ready``)
  and report per-layer forward/backward wall times. This is what
  ``getTimes`` measured, and it localises hotspots the fused step hides.
- :func:`trace` — a ``jax.profiler`` xplane trace of the real fused program
  for TensorBoard/xprof, which is where fused-step truth lives.

Facade integration: while a :func:`profiled` context is active, every
stateful ``Module.forward``/``backward`` call accumulates synchronised wall
time into the module's ``_times`` counters; ``Module.get_times()`` /
``reset_times()`` read them (API parity with ``getTimes:167``).
"""

from __future__ import annotations

import contextlib
import time

import jax
import jax.numpy as jnp

_ENABLED = False


class DecodeCounters(dict):
    """Compile/dispatch telemetry shared by the jitted decode paths.

    A plain dict of named counters (callers read it exactly like the old
    gpt.py-local ``decode_stats``) with two increment idioms that exploit
    how jit works:

    - :meth:`tick` placed INSIDE a function being traced by ``jax.jit``
      runs at trace time only, so it counts XLA compilations, not calls;
    - :meth:`dispatched` runs on the host once per call, so it counts
      executable launches.

    The ratio of the two is the whole point of the KV-cache/serving
    designs (compile O(1) times, dispatch O(1) per token), and the
    regression tests gate on these values — ``GPTForCausalLM.decode_stats``
    and ``serving.SlotManager.stats`` are both instances.

    ``obs_name`` additionally publishes the counters on the obs default
    registry as scrape-time *collector* samples
    (``bigdl_decode_traces{source=..., kind=...}`` /
    ``bigdl_decode_dispatches{source=...}``), so a compile storm shows
    up live at ``/metrics``. Collector, not per-event mutation, because
    :meth:`tick` runs INSIDE jit traces where registry calls are
    forbidden (the ``span-in-jit`` lint rule); the registry samples the
    dict from the scrape thread instead. Registration holds only a
    weakref — dead instances prune themselves at the next scrape.

    Cost accounting rides the same instance as plain *attributes*
    (``flops`` / ``hbm_bytes``, fed by :meth:`add_cost` from
    :class:`CostStampedJit` dispatches) — attributes, not dict keys,
    because the dict IS the public counter namespace the collector and
    the compile-gate tests enumerate. When costs are flowing the
    collector derives ``bigdl_device_flops_per_sec`` /
    ``bigdl_hbm_bytes_per_sec`` rates between scrapes and, when the
    device kind has a known peak, a live ``bigdl_mfu`` gauge.
    """

    _obs_seq = None  # lazily an itertools.count (shared across instances)

    def __init__(self, *trace_keys, obs_name=None):
        super().__init__({k: 0 for k in trace_keys})
        self["dispatches"] = 0
        self.flops = 0.0
        self.hbm_bytes = 0.0
        if obs_name is not None:
            self._register_obs(obs_name)

    def _register_obs(self, obs_name):
        import itertools
        import weakref
        from bigdl_tpu import obs
        if DecodeCounters._obs_seq is None:
            DecodeCounters._obs_seq = itertools.count()
        source = f"{obs_name}-{next(DecodeCounters._obs_seq)}"
        ref = weakref.ref(self)
        rate_state = {}

        def collect():
            counters = ref()
            if counters is None:
                return None   # instance gone: unregister this collector
            # ``*_traces`` count compilations; any other key is a running
            # sum its owner keeps beside them (the slot table's
            # ``moe_assignments`` / ``moe_experts_hit``)
            samples = [("bigdl_decode_traces" if k.endswith("_traces")
                        else "bigdl_decode_sums",
                        {"source": source, "kind": k}, v)
                       for k, v in counters.items() if k != "dispatches"]
            samples.append(("bigdl_decode_dispatches", {"source": source},
                            counters["dispatches"]))
            if counters.flops > 0.0:
                lbl = {"source": source}
                samples.append(("bigdl_device_flops", lbl, counters.flops))
                samples.append(("bigdl_hbm_bytes", lbl,
                                counters.hbm_bytes))
                now = time.monotonic()
                prev = rate_state.get("prev")
                rate_state["prev"] = (now, counters.flops,
                                      counters.hbm_bytes)
                if prev is not None and now > prev[0]:
                    dt = now - prev[0]
                    flops_rate = max(0.0, counters.flops - prev[1]) / dt
                    samples.append(("bigdl_device_flops_per_sec", lbl,
                                    flops_rate))
                    samples.append(("bigdl_hbm_bytes_per_sec", lbl,
                                    max(0.0,
                                        counters.hbm_bytes - prev[2]) / dt))
                    peak = device_peak_flops()
                    if peak:
                        samples.append(("bigdl_mfu", lbl,
                                        flops_rate / peak))
            return samples

        obs.default_registry().register_collector(collect)

    def tick(self, name):
        """Count one compilation (call inside the traced body only)."""
        self[name] += 1

    def dispatched(self, n=1):
        """Count ``n`` executable launches (call on the host per call)."""
        self["dispatches"] += n

    def add(self, name, value):
        """Add to a running sum that its owner created beside the gates
        (host side, the owner's thread)."""
        self[name] += value

    def add_cost(self, flops, hbm_bytes):
        """Accumulate one dispatch's modeled device work (host side;
        fed by :class:`CostStampedJit` from the executable's
        compile-time ``cost_analysis``)."""
        self.flops += flops
        self.hbm_bytes += hbm_bytes


# Peak dense bf16 FLOPS per chip by device kind, for the live MFU gauge
# (public TPU spec-sheet numbers). Unknown kinds (CPU fallback, new
# hardware) return None and the MFU gauge is omitted, never fabricated.
_PEAK_FLOPS = {
    "tpu v2": 45e12,
    "tpu v3": 123e12,
    "tpu v4": 275e12,
    "tpu v4 lite": 138e12,
    "tpu v5": 459e12,
    "tpu v5p": 459e12,
    "tpu v5 lite": 197e12,
    "tpu v5e": 197e12,
    "tpu v6 lite": 918e12,
    "tpu v6e": 918e12,
}
_peak_cache = []


def device_peak_flops():
    """Peak dense bf16 FLOPS of ``jax.devices()[0]``'s kind, or None
    when the kind is unknown (memoized after the first lookup)."""
    if not _peak_cache:
        try:
            kind = jax.devices()[0].device_kind
        except Exception:
            kind = ""
        _peak_cache.append(_PEAK_FLOPS.get(str(kind).strip().lower()))
    return _peak_cache[0]


def _executable_cost(compiled):
    """(flops, bytes_accessed) from a compiled executable's
    ``cost_analysis`` — 0.0s when the backend reports nothing (the
    gauges then simply stay silent)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return 0.0, 0.0
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return 0.0, 0.0
    try:
        return (float(ca.get("flops", 0.0) or 0.0),
                float(ca.get("bytes accessed", 0.0) or 0.0))
    except (TypeError, ValueError):
        return 0.0, 0.0


class CostStampedJit:
    """A ``jax.jit`` wrapper that AOT-compiles per argument-shape
    signature and stamps each executable with its compile-time
    ``cost_analysis()`` flops/bytes, accumulating them into a
    :class:`DecodeCounters` on every dispatch — the input to the live
    ``bigdl_mfu``/bandwidth gauges.

    Compile behavior is identical to the lazy jit it replaces:
    ``lower(*args)`` traces exactly once per new signature (any
    ``tick`` inside the body fires there, so the compile-gate tests
    see the same counts), and the cached ``compiled`` dispatches with
    ZERO further traces — numpy args, python scalars and donated
    buffers all verified to rebind without retracing. Serving call
    sites only wrap when request tracing is enabled; flag-off keeps
    the raw jit functions and is byte-identical.
    """

    __slots__ = ("_jit", "_counters", "_compiled")

    def __init__(self, fn, counters=None, **jit_kwargs):
        # accept a raw callable (jitted here) or an existing jax.jit
        # wrapper (identified by its .lower) so call sites keep their
        # own donate_argnums/out_shardings construction
        self._jit = fn if hasattr(fn, "lower") else jax.jit(fn,
                                                            **jit_kwargs)
        self._counters = counters
        self._compiled = {}

    @staticmethod
    def _leaf_sig(leaf):
        shape = getattr(leaf, "shape", None)
        if shape is None:        # python scalar: weak-typed under trace
            return (type(leaf).__name__,)
        return (tuple(shape), str(getattr(leaf, "dtype", "?")))

    def signature(self, args):
        return tuple(self._leaf_sig(leaf)
                     for leaf in jax.tree_util.tree_leaves(args))

    @property
    def executables(self):
        """{signature: (flops, bytes)} for every compiled variant."""
        return {sig: cost for sig, (_, cost) in self._compiled.items()}

    def __call__(self, *args):
        sig = self.signature(args)
        entry = self._compiled.get(sig)
        if entry is None:
            compiled = self._jit.lower(*args).compile()
            entry = self._compiled[sig] = (compiled,
                                           _executable_cost(compiled))
        compiled, (flops, hbm_bytes) = entry
        out = compiled(*args)
        if self._counters is not None and (flops or hbm_bytes):
            self._counters.add_cost(flops, hbm_bytes)
        return out


def _trace_annotation(name, attrs):
    return jax.profiler.TraceAnnotation(name, **attrs)


def install_trace_annotator():
    """Make ``jax.profiler.TraceAnnotation`` the default span tracer's
    annotator (``obs/spans.py``: ``obs`` itself imports no jax), so that
    every leaf span also lies on its thread's line of the profiler's
    ``/host:CPU`` plane, on the device trace's clock, while a profiler
    session runs; with no session an annotation costs about a
    microsecond. ``bigdl_tpu.serving`` and ``bigdl_tpu.optim`` call this
    when they are imported."""
    from bigdl_tpu import obs
    obs.default_tracer().annotator = _trace_annotation


def profiling_enabled():
    return _ENABLED


@contextlib.contextmanager
def profiled():
    """While active, facade forward/backward calls accumulate wall time on
    each module they are invoked on (synchronising after each call)."""
    global _ENABLED
    prev, _ENABLED = _ENABLED, True
    try:
        yield
    finally:
        _ENABLED = prev


@contextlib.contextmanager
def trace(logdir):
    """Device-level trace of the fused program (jax.profiler xplane; view in
    TensorBoard's profile plugin / xprof)."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _sync(tree):
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()


def per_layer_times(module, x, rng=None, repeats=3, _prefix=None):
    """Forward+backward wall time per layer (reference ``getTimes`` shape:
    a list of ``(name, forward_seconds, backward_seconds)``).

    Sequential containers are walked into; any other module (leaf, Graph,
    Concat, ...) is timed as one unit. Times are medians over ``repeats``
    runs after one warmup, fully synchronised, on whatever backend the
    arrays live on.
    """
    from bigdl_tpu.nn.containers import Sequential

    module._ensure_built(x)
    entries = []
    name = _prefix or module.name

    if isinstance(module, Sequential):
        cur = x
        for i, child in enumerate(module.modules):
            sub, cur = per_layer_times(child, cur, rng=rng, repeats=repeats,
                                       _prefix=f"{name}[{i}]:{child.name}")
            entries.extend(sub)
        return (entries, cur) if _prefix else entries

    def timed(fn, *args):
        fn(*args)  # warmup (compile)
        samples = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = fn(*args)
            _sync(out)
            samples.append(time.perf_counter() - t0)
        return sorted(samples)[len(samples) // 2], out

    was_training = module.train_mode
    fwd_s, out = timed(lambda v: module.forward(v, rng=rng), x)
    cot = jax.tree_util.tree_map(jnp.ones_like, out)
    bwd_s, _ = timed(lambda v: module.backward(v, cot), x)
    if not was_training:
        module.evaluate()
    entries.append((name, fwd_s, bwd_s))
    return (entries, out) if _prefix else entries


def format_times(entries):
    """Human-readable table of :func:`per_layer_times` output."""
    total_f = sum(e[1] for e in entries)
    total_b = sum(e[2] for e in entries)
    lines = [f"{'layer':<44} {'fwd_ms':>9} {'bwd_ms':>9}"]
    for name, f, b in entries:
        lines.append(f"{name:<44} {f * 1e3:>9.3f} {b * 1e3:>9.3f}")
    lines.append(f"{'TOTAL':<44} {total_f * 1e3:>9.3f} {total_b * 1e3:>9.3f}")
    return "\n".join(lines)
