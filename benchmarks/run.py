"""The benchmark's command.

    python benchmarks/run.py --workload W --seed N --seconds S --trace 0|1

Finds the cell ``W`` in ``BENCHMARK.json``, its traffic file
(``benchmarks/workloads/<traffic>.json``), its configuration's file, the
runner the configuration names (``benchmarks/runners/<runner>.py``) and one
reader per metric (``benchmarks/metrics/<metric>.py``, ``read(ctx)``).
Nothing here names a cell, a configuration or a metric: a later PR adds
files and manifest entries and edits nothing that is there.

Runs on the machine it is started on, in one process, on a TPU only:
without one, or with fewer chips than the cell asks for, it exits non-zero
and prints no result. ``--rehearse`` is not a fallback: it shrinks the
configuration (the file's ``rehearse`` overrides) to drive the same code
on the CPU, names the platform, and fills in no metric.

The last line of stdout is the result: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: every number compared, beside its limit (also the last
lines of stderr).
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()          # process start, as near as Python gives

import argparse                     # noqa: E402
import importlib                    # noqa: E402
import importlib.util               # noqa: E402
import json                         # noqa: E402
import math                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Ctx:
    """What a runner fills and a metric reader gets."""

    def __init__(self, **kw):
        self.cell = self.config = self.traffic = None
        self.seed = self.seconds = self.chips = None
        self.rehearse = False
        self.peaks = None
        self.device = None
        self.until_window_s = None
        self.window = None          # (start, end) on perf_counter
        self.records = []           # serving: loadgen.Record
        self.counters = {}          # the runner's and the program's counts
        self.spans = []             # program spans inside the traced part
        self.trace = None           # trace_reduce.Reduced
        self.traced = None          # (start, end) of the traced part, perf_counter
        self.notes = {}             # what a reader wants printed beside it
        self.phases = []            # (name, perf_counter) marks of set-up and after
        self.__dict__.update(kw)

    def mark(self, name):
        """A named instant, printed as seconds since the one before."""
        self.phases.append((name, time.perf_counter()))

    def phase_seconds(self, origin):
        out, last = {}, origin
        for name, t in self.phases:
            out[name] = round(t - last, 3)
            last = t
        return out


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def _merge(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def load_cell(name, root=ROOT, rehearse=False):
    """``(manifest, cell, configuration, traffic)`` of the cell ``name``."""
    manifest = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {c["name"]: c for c in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(has {sorted(cells)})")
    cell = cells[name]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = _load_json(os.path.join(root, entry["file"]))
    bench_dir = os.path.join(root, manifest["paths"][0])
    traffic = _load_json(os.path.join(bench_dir, "workloads",
                                      cell["traffic"] + ".json"))
    if rehearse:
        config = _merge(config, config.get("rehearse", {}))
        traffic = _merge(traffic, traffic.get("rehearse", {}))
    return manifest, cell, config, traffic, bench_dir


def metrics_for(manifest, cell, group):
    """The metrics of ``group`` (``end_to_end`` / ``per_layer``) that this
    cell reports: those without a ``workloads`` key, where the cell reports
    the metric they move, and those that list the cell."""
    e2e = [m["name"] for m in manifest["end_to_end"]
           if "workloads" not in m or cell["name"] in m["workloads"]]
    if group == "end_to_end":
        return [m for m in manifest["end_to_end"] if m["name"] in e2e]
    return [m for m in manifest["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def load_reader(bench_dir, metric):
    path = os.path.join(bench_dir, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def find_device(chips, rehearse):
    """The device record, or exit: a measurement finds a TPU with enough
    chips or it does not run."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"benchmarks/run.py: JAX found no device: {e}")
    dev = devs[0]
    if not rehearse:
        if dev.platform != "tpu":
            raise SystemExit(f"benchmarks/run.py measures on a TPU only; JAX "
                             f"found platform {dev.platform!r}")
        if len(devs) < chips:
            raise SystemExit(f"benchmarks/run.py: the cell asks for {chips} "
                             f"chip(s), JAX found {len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


class MemoryWatch:
    """The peak of device memory on the fullest chip. The allocator keeps
    two pools apart: buffers (``bytes_in_use``, whose peak it tracks) and
    what a loaded program reserves for its scratch (``bytes_reserved``).
    Their sum is what the chip holds, and the allocator tracks no peak of
    it, so it is sampled twice a second while the window runs; the result
    is never under the allocator's own peak of buffers."""

    def __init__(self, period_s=0.5):
        import threading
        self.period_s, self.peak = period_s, 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, name="bench-mem",
                                        daemon=True)

    @staticmethod
    def _read():
        import jax
        held = peak = 0
        for d in jax.devices():
            stats = d.memory_stats() or {}
            held = max(held, int(stats.get("bytes_in_use", 0))
                       + int(stats.get("bytes_reserved", 0)))
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        return held, peak

    def _loop(self):
        while not self._stop.wait(self.period_s):
            self.peak = max(self.peak, self._read()[0])

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        self.peak = max(self.peak, *self._read())


def _cache_state():
    """Where the persistent compile cache is, and how full."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or jax.config.jax_compilation_cache_dir)
    try:
        files = [os.path.join(path, f) for f in os.listdir(path)]
        size = sum(os.path.getsize(f) for f in files if os.path.isfile(f))
    except (OSError, TypeError):
        return {"dir": path}
    return {"dir": path, "entries": len(files), "mbytes": round(size / 2**20, 1)}


def _finite(v):
    return isinstance(v, (int, float)) and math.isfinite(v)


def _within(checks):
    """The one comparison that decides ``correct``: every number that has
    a limit is finite and not above it."""
    return all(c["limit"] is None or (_finite(c["value"])
                                      and c["value"] <= c["limit"])
               for c in checks)


def run(args):
    from benchmarks.harness import peaks as peaks_mod
    from benchmarks.harness import profiler as prof
    from benchmarks.harness import trace_reduce

    manifest, cell, config, traffic, bench_dir = load_cell(
        args.workload, rehearse=args.rehearse)
    t_args = time.perf_counter()
    device = find_device(int(cell["chips"]), args.rehearse)
    from bigdl_tpu.utils.compile_cache import enable_persistent_cache
    enable_persistent_cache()

    ctx = Ctx(cell=cell, config=config, traffic=traffic, seed=args.seed,
              seconds=float(args.seconds), chips=int(cell["chips"]),
              rehearse=args.rehearse, device=device,
              peaks=None if args.rehearse
              else peaks_mod.peaks_for(device["kind"]))
    ctx.phases = [("imports", t_args), ("device", time.perf_counter())]
    runner_mod = importlib.import_module("benchmarks.runners."
                                         + config["runner"])
    runner = runner_mod.Runner(ctx)
    profiler = prof.Profiler(float(traffic.get(
        "trace_seconds", prof.TRACE_SECONDS))) if args.trace else None

    with prof.compile_log() as compiled:
        runner.setup()
        if args.sweep:
            return sweep(runner, ctx, args, manifest, cell, bench_dir)
        with MemoryWatch() as memory:
            start, end = runner.run_window(profiler)
    ctx.until_window_s = start - _T0
    ctx.counters["compiles_in_window"] = sum(1 for t, _, _ in compiled
                                             if start <= t <= end)
    before = sorted(((d, n) for t, n, d in compiled if t < start),
                    reverse=True)
    ctx.notes["setup_compiles"] = {
        "count": len(before), "seconds": round(sum(d for d, _ in before), 2),
        "longest": [[n, round(d, 2)] for d, n in before[:4]],
        "cache": _cache_state()}
    ctx.mark("window_and_drain")
    peak = memory.peak
    runner.release()
    ctx.mark("release")
    if profiler is not None:
        ctx.trace = profiler.reduce()
        ctx.mark("reduce_trace")
    attempted, failed, checks, controls = runner.verify(
        with_control=args.control)
    ctx.mark("verify_end")
    correct = _within(checks)
    device = dict(device, memory_peak_bytes=peak)
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": {}, "device": device}
    group = "per_layer" if args.trace else "end_to_end"
    for m in metrics_for(manifest, cell, group):
        # a rehearsal on the CPU fills counts only: never a time, a rate or
        # a share under the name of a device metric
        if not args.rehearse or m["source"] == "program_counter":
            value = load_reader(bench_dir, m["name"])(ctx)
            if value is not None and _finite(value):
                result["metrics"][m["name"]] = {"value": float(value),
                                                "unit": m["unit"]}
    if ctx.trace is not None and not args.rehearse:
        busy_window = trace_reduce.busy_and_window(ctx.trace, ctx.traced)
        if busy_window is not None:
            device["busy_s"], device["window_s"] = busy_window
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(ctx.trace),
            "idle_gaps": trace_reduce.idle_gaps(ctx.trace)}
        result["modules"] = trace_reduce.module_names(ctx.trace)
    if args.rehearse:
        result["rehearsal"] = True
    result["notes"] = dict(ctx.notes, seed=args.seed, seconds=args.seconds,
                           post_window_s=time.perf_counter() - end,
                           phases_s=ctx.phase_seconds(_T0))
    if args.control:
        # each control and planted fault through the same comparison: it
        # has to come out NOT correct
        result["controls"] = {name: {"correct_if_control": _within(theirs),
                                     "checks": theirs}
                              for name, theirs in controls.items()}
    result["checks"] = checks
    return result


def sweep(runner, ctx, args, manifest, cell, bench_dir):
    """One window at each of ``--sweep``'s rates in the one process, to
    find the knee ONCE when a cell is defined: the highest rate at which
    the backlog does not grow over the window. Prints one line a rate and
    no result line: a sweep is not a measurement."""
    from benchmarks.harness import window
    readers = {m["name"]: load_reader(bench_dir, m["name"])
               for m in metrics_for(manifest, cell, "end_to_end")}
    for rate in args.sweep:
        ctx.traffic = _merge(ctx.traffic, {"rate_per_s": rate})
        runner.replan()
        start, end = runner.run_window(None)
        recs = window.due_in(ctx.records, ctx.window)
        half = (start + end) / 2
        first = [r.token_at[0] - r.due_at for r in recs
                 if r.token_at and r.due_at < half]
        second = [r.token_at[0] - r.due_at for r in recs
                  if r.token_at and r.due_at >= half]
        open_at_end = sum(1 for r in recs
                          if not r.token_at or r.token_at[-1] > end)
        line = {"rate_per_s": rate, "attempted": len(recs),
                "open_at_window_end": open_at_end,
                "first_token_ms_mean_first_half": 1e3 * sum(first) / max(len(first), 1),
                "first_token_ms_mean_second_half":
                    1e3 * sum(second) / max(len(second), 1),
                "first_token_ms_p50": 1e3 * (window.percentile(
                    window.first_token_delays(recs, ctx.window), 50) or 0)}
        ctx.until_window_s = start - _T0
        for name, read in readers.items():
            line[name] = read(ctx)
        print("sweep " + json.dumps(line), flush=True)
    runner.release()
    return None


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on the CPU; no metric is filled")
    ap.add_argument("--sweep", default=None, metavar="RATES",
                    type=lambda s: [float(x) for x in s.split(",")],
                    help="open loop: one window at each of these "
                         "comma-separated rates, in one process, to find "
                         "the knee; prints no result line")
    ap.add_argument("--control", action="store_true",
                    help="also put each lower-precision control and planted "
                         "fault through the comparison (correct_if_control)")
    args = ap.parse_args(argv)
    if args.seconds is None:
        args.seconds = _load_json(os.path.join(ROOT, "BENCHMARK.json"))[
            "run_seconds"]
    result = run(args)
    if result is None:
        return 0
    for name, theirs in result.get("controls", {}).items():
        print(f"control {name}: correct_if_control "
              f"{theirs['correct_if_control']} " + ", ".join(
                  f"{c['name']} {c['value']:.6g} (limit {c['limit']})"
                  for c in theirs["checks"]), file=sys.stderr)
    for c in result["checks"]:
        print(f"check {c['name']}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    # daemon reader threads and the program's pools must not hold the exit
    os._exit(code)
