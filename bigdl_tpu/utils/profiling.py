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
    """

    _obs_seq = None  # lazily an itertools.count (shared across instances)

    def __init__(self, *trace_keys, obs_name=None):
        super().__init__({k: 0 for k in trace_keys})
        self["dispatches"] = 0
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
