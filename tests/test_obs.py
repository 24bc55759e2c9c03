"""Unit tests for bigdl_tpu.obs: registry semantics, Prometheus text
exposition conformance, span nesting (same-thread and cross-thread),
ring-buffer bounding under soak, exporters, the kill switch, and the
rolling-median anomaly detector.

Everything here runs against FRESH MetricsRegistry/SpanTracer instances
(never the process-global defaults) so tests stay independent of
whatever instrumented code ran earlier in the pytest process.
"""

import gc
import json
import re
import threading
import urllib.request

import pytest

from bigdl_tpu import obs
from bigdl_tpu.obs.metrics import MetricsRegistry
from bigdl_tpu.obs.spans import SpanTracer


@pytest.fixture
def reg():
    return MetricsRegistry()


@pytest.fixture
def tracer():
    return SpanTracer(capacity=256)


# ------------------------------------------------------------------ registry

def test_counter_and_gauge_basics(reg):
    c = reg.counter("requests_total", "requests", labels=("route",))
    c.labels("a").inc()
    c.labels("a").inc(3)
    c.labels(route="b").inc()
    assert c.labels("a").value == 4
    assert c.labels("b").value == 1
    with pytest.raises(ValueError, match="only go up"):
        c.labels("a").inc(-1)
    g = reg.gauge("depth")
    g.set(7)
    g.dec(2)
    assert g.value == 5


def test_get_or_create_is_idempotent_and_typed(reg):
    a = reg.counter("x_total", labels=("k",))
    b = reg.counter("x_total", labels=("k",))
    assert a is b
    assert a.labels("v") is b.labels("v")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total", labels=("k",))
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("x_total", labels=("other",))
    with pytest.raises(ValueError, match="label value"):
        a.labels("v", "extra")
    with pytest.raises(ValueError, match="invalid metric"):
        reg.counter("bad-name")


def test_histogram_bucket_invariants(reg):
    h = reg.histogram("lat_seconds", buckets=(0.1, 0.5, 1.0))
    for v in (0.05, 0.1, 0.3, 0.9, 5.0):
        h.observe(v)
    cum, s, c = h._solo().snapshot()
    # le is inclusive: 0.1 lands in the le="0.1" bucket
    assert cum == [2, 3, 4, 5]
    assert c == 5
    assert s == pytest.approx(6.35)
    # cumulative counts are monotone and end at count
    assert all(a <= b for a, b in zip(cum, cum[1:]))
    assert h.quantile(0.0) is not None
    assert 0.0 < h.quantile(0.5) <= 1.0
    # values past the last finite bound clamp to it
    assert h.quantile(1.0) == 1.0
    with pytest.raises(ValueError):
        h.quantile(1.5)
    with pytest.raises(ValueError, match="buckets"):
        reg.histogram("lat_seconds", buckets=(1.0, 2.0))


def test_histogram_quantile_edge_cases(reg):
    empty = reg.histogram("e_seconds", buckets=(0.5, 1.0))
    # no observations: None at every q, never a fabricated 0.0
    assert empty.quantile(0.0) is None
    assert empty.quantile(0.5) is None
    assert empty.quantile(1.0) is None

    first = reg.histogram("f_seconds", buckets=(1.0, 2.0))
    for _ in range(3):
        first.observe(0.5)
    # all mass in the first bucket: interpolate from its 0.0 lower edge
    assert first.quantile(0.0) == 0.0
    assert first.quantile(0.5) == pytest.approx(0.5)
    assert first.quantile(1.0) == pytest.approx(1.0)

    later = reg.histogram("l_seconds", buckets=(0.5, 1.0, 2.0))
    later.observe(0.7)
    # q=0 is the minimum's bucket lower edge, not a blanket 0.0
    assert later.quantile(0.0) == 0.5

    neg = reg.histogram("n_seconds", buckets=(-1.0, 2.0))
    neg.observe(-5.0)
    # a non-positive first bound cannot interpolate from 0: the bound
    assert neg.quantile(0.5) == -1.0

    past = reg.histogram("p_seconds", buckets=(0.5, 1.0))
    past.observe(9.0)
    # everything in +Inf clamps to the last finite bound, q=0 included
    assert past.quantile(0.0) == 1.0
    assert past.quantile(0.5) == 1.0
    assert past.quantile(1.0) == 1.0


def test_prometheus_exposition_conformance(reg):
    c = reg.counter("steps_total", "steps so far", labels=("loop",))
    c.labels("local").inc(3)
    h = reg.histogram("ttft_seconds", "ttft", buckets=(0.5, 1.0))
    h.observe(0.2)
    h.observe(0.7)
    h.observe(9.0)
    text = reg.prometheus_text()
    assert "# HELP steps_total steps so far\n" in text
    assert "# TYPE steps_total counter\n" in text
    assert 'steps_total{loop="local"} 3\n' in text
    assert "# TYPE ttft_seconds histogram\n" in text
    assert 'ttft_seconds_bucket{le="0.5"} 1\n' in text
    assert 'ttft_seconds_bucket{le="1"} 2\n' in text
    assert 'ttft_seconds_bucket{le="+Inf"} 3\n' in text
    assert "ttft_seconds_count 3\n" in text
    assert re.search(r"ttft_seconds_sum 9\.9\b", text)
    # every non-comment line is `name{labels} value` or `name value`
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        assert re.fullmatch(
            r'[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? \S+', line), line


def test_prometheus_exposition_round_trip(reg):
    """Conformance round-trip: parse our own /metrics page back into
    (name, labels, value) samples with a spec-shaped grammar, then
    re-serialize through the SAME escaping/formatting helpers — the
    output must be byte-identical. Catches one-way escaping bugs a
    substring check can't (e.g. values that parse but re-serialize
    differently)."""
    from bigdl_tpu.obs.metrics import _fmt_labels, _fmt_value
    c = reg.counter("steps_total", "steps so far", labels=("loop",))
    c.labels("local").inc(3)
    reg.gauge("weird", labels=("path",)).labels('C:\\tmp\n"x"').set(1.5)
    h = reg.histogram("ttft_seconds", "ttft", buckets=(0.5, 1.0))
    for v in (0.2, 0.7, 9.0):
        h.observe(v, exemplar="tr-1")
    text = reg.prometheus_text()

    def unescape(s):
        out, i = [], 0
        while i < len(s):
            if s[i] == "\\":
                out.append({"n": "\n", '"': '"', "\\": "\\"}[s[i + 1]])
                i += 2
            else:
                out.append(s[i])
                i += 1
        return "".join(out)

    lines = []
    for line in text.splitlines():
        if line.startswith("#"):
            lines.append(line)
            continue
        m = re.fullmatch(
            r'([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})? (\S+)', line)
        assert m, f"unparseable exposition line: {line!r}"
        name, labelstr, value = m.groups()
        pairs = ()
        if labelstr:
            pairs = tuple(
                (k, unescape(v)) for k, v in
                re.findall(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"',
                           labelstr))
        lines.append(f"{name}{_fmt_labels(pairs)} "
                     f"{_fmt_value(float(value))}")
    assert "\n".join(lines) + "\n" == text


def test_label_escaping(reg):
    g = reg.gauge("weird", labels=("path",))
    g.labels('C:\\tmp\n"x"').set(1)
    text = reg.prometheus_text()
    assert 'path="C:\\\\tmp\\n\\"x\\""' in text
    # round-trip: the escaped text is a single line
    assert len([ln for ln in text.splitlines()
                if ln.startswith("weird{")]) == 1


def test_collectors_sample_and_self_unregister(reg):
    alive = {"on": True}

    def collect():
        if not alive["on"]:
            return None
        return [("ext_value", {"src": "a"}, 42)]

    reg.register_collector(collect)
    assert 'ext_value{src="a"} 42' in reg.prometheus_text()
    assert reg.snapshot()["ext_value"]["series"][0]["value"] == 42
    alive["on"] = False
    assert "ext_value" not in reg.prometheus_text()
    assert collect not in reg._collectors     # pruned


def test_decode_counters_publish_as_collector():
    from bigdl_tpu.utils.profiling import DecodeCounters
    stats = DecodeCounters("prefill_traces", "step_traces",
                           obs_name="obstest")
    stats.tick("step_traces")
    stats.dispatched(5)
    text = obs.default_registry().prometheus_text()
    src = [ln for ln in text.splitlines()
           if "obstest" in ln and "bigdl_decode" in ln]
    assert any('kind="step_traces"' in ln and ln.endswith(" 1")
               for ln in src)
    assert any("bigdl_decode_dispatches" in ln and "} 5" in ln
               for ln in src)
    name = re.search(r'source="(obstest-\d+)"', src[0]).group(1)
    del stats, src
    gc.collect()
    # dead instance: the weakref collector prunes itself at the next scrape
    assert name not in obs.default_registry().prometheus_text()


def test_registry_json_snapshot(reg):
    reg.counter("a_total").inc()
    h = reg.histogram("b_seconds", buckets=(1.0,))
    h.observe(0.5)
    snap = json.loads(reg.json())
    assert snap["metrics"]["a_total"]["series"][0]["value"] == 1
    hist = snap["metrics"]["b_seconds"]["series"][0]
    assert hist["count"] == 1 and hist["p50"] is not None


def test_kill_switch_no_ops_everything(reg, tracer):
    prev = obs.set_enabled(False)
    try:
        c = reg.counter("dead_total")
        c.inc(10)
        reg.gauge("dead_gauge").set(3)
        reg.histogram("dead_seconds").observe(1.0)
        with tracer.span("dead/span"):
            pass
        tracer.record("dead/record", 0.0, 1.0)
        assert c.value == 0
        assert reg.gauge("dead_gauge").value == 0
        assert len(tracer) == 0
    finally:
        obs.set_enabled(prev)
    c.inc()
    assert c.value == 1


# --------------------------------------------------------------------- spans

def test_span_nesting_same_thread(tracer):
    with tracer.span("outer", step=1):
        with tracer.span("inner"):
            pass
    with tracer.span("after"):
        pass
    spans = tracer.spans()
    assert [(s.name, s.parent, s.depth) for s in spans] == [
        ("inner", "outer", 1), ("outer", None, 0), ("after", None, 0)]
    inner, outer, _ = spans
    assert outer.start <= inner.start and inner.end <= outer.end
    assert outer.attrs == {"step": 1}


def test_span_nesting_is_per_thread(tracer):
    """A scheduler-style worker thread's spans must not nest under a
    client thread's open span (and vice versa)."""
    ready = threading.Event()
    release = threading.Event()

    def worker():
        with tracer.span("worker/step"):
            with tracer.span("worker/dispatch"):
                ready.set()
                release.wait(5)

    t = threading.Thread(target=worker, name="sched-thread")
    with tracer.span("client/submit"):
        t.start()
        assert ready.wait(5)
        release.set()
        t.join(5)
    by_name = {s.name: s for s in tracer.spans()}
    assert by_name["worker/step"].parent is None
    assert by_name["worker/step"].depth == 0
    assert by_name["worker/dispatch"].parent == "worker/step"
    assert by_name["client/submit"].parent is None
    assert by_name["worker/step"].thread_name == "sched-thread"
    assert (by_name["client/submit"].thread_id
            != by_name["worker/step"].thread_id)


def test_ring_buffer_bounds_under_soak():
    tracer = SpanTracer(capacity=64)
    for i in range(10_000):
        tracer.record(f"s{i}", 0.0, 0.001, i=i)
    assert len(tracer) == 64
    names = [s.name for s in tracer.spans()]
    assert names == [f"s{i}" for i in range(9936, 10_000)]
    tracer.set_capacity(16)
    assert len(tracer) == 16
    assert tracer.spans()[-1].name == "s9999"


def test_chrome_trace_export(tmp_path, tracer):
    with tracer.span("train/dispatch", step=3):
        with tracer.span("train/drain"):
            pass
    path = tracer.export(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {e["name"] for e in events} == {"train/dispatch", "train/drain"}
    drain = next(e for e in events if e["name"] == "train/drain")
    assert drain["args"]["parent"] == "train/dispatch"
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in events)
    assert meta and meta[0]["name"] == "thread_name"
    assert doc["displayTimeUnit"] == "ms"


def test_record_span_after_the_fact(tracer):
    tracer.record("train/feed", 10.0, 10.25, neval=2)
    (s,) = tracer.spans()
    assert s.duration == pytest.approx(0.25)
    assert s.attrs == {"neval": 2}


# ------------------------------------------------- the second sink: leaves

class _FakeAnnotation:
    def __init__(self, log, name, attrs):
        self.log, self.name, self.attrs = log, name, dict(attrs)

    def __enter__(self):
        self.log.append(("enter", self.name, self.attrs))
        return self

    def __exit__(self, *exc):
        self.log.append(("leave", self.name, self.attrs))
        return False


@pytest.fixture
def annotated():
    """``(tracer, log)``: a fresh tracer whose annotator writes what it
    is asked to enter and leave into ``log``."""
    log = []
    return SpanTracer(capacity=64, annotator=lambda name, attrs:
                      _FakeAnnotation(log, name, attrs)), log


def test_leaf_span_enters_and_leaves_the_annotator(annotated):
    tracer, log = annotated
    with tracer.span("serve/step", iter=3, live=2):           # a parent
        with tracer.leaf_span("serve/step.dispatch", iter=3):
            pass
        with tracer.leaf_span("serve/step.readback", iter=3) as leaf:
            leaf.set(late=1)       # the ring gets it, the event does not
    assert log == [("enter", "serve/step.dispatch", {"iter": 3}),
                   ("leave", "serve/step.dispatch", {"iter": 3}),
                   ("enter", "serve/step.readback", {"iter": 3}),
                   ("leave", "serve/step.readback", {"iter": 3})]
    by_name = {s.name: s for s in tracer.spans()}
    assert by_name["serve/step.readback"].attrs == {"iter": 3, "late": 1}
    assert by_name["serve/step.dispatch"].parent == "serve/step"
    assert by_name["serve/step"].attrs == {"iter": 3, "live": 2}


@pytest.mark.parametrize("kind", ["parent", "record", "record_at",
                                  "leaf_while_disabled"])
def test_only_an_enabled_leaf_reaches_the_annotator(annotated, kind):
    tracer, log = annotated
    if kind == "parent":
        with tracer.span("serve/prefill", n=2):
            pass
    elif kind == "record":
        tracer.record("train/feed", 10.0, 10.25)
    elif kind == "record_at":
        tracer.record_at("serve/queue_wait", tracer.epoch_perf + 1.0,
                         tracer.epoch_perf + 1.5, request=7)
    else:
        prev = obs.set_enabled(False)
        try:
            with tracer.leaf_span("serve/idle", iter=1):
                pass
        finally:
            obs.set_enabled(prev)
    assert log == []
    assert len(tracer) == (0 if kind == "leaf_while_disabled" else 1)


def test_record_at_places_a_span_where_its_readings_were_taken(tracer):
    a = tracer.epoch_perf + 2.0
    with tracer.span("outer"):
        tracer.record_at("serve/queue_wait", a, a + 0.5, request=1)
        tracer.record_at("serve/first_token", a + 0.5, a + 0.75, request=1)
    wait, first, _ = tracer.spans()
    assert (wait.start, wait.end) == (2.0, 2.5)
    assert first.start == wait.end and first.duration == 0.25
    assert wait.parent is None and wait.depth == 0   # no thread owns it


def test_a_span_is_timed_whatever_the_kill_switch_says(tracer):
    """The scheduler takes a decode block's seconds from its span, and
    ``engine.metrics()`` reports them with telemetry off as well."""
    prev = obs.set_enabled(False)
    try:
        with tracer.span("serve/step") as sp:
            pass
    finally:
        obs.set_enabled(prev)
    assert sp.end >= sp.start > 0.0 and sp.duration == sp.end - sp.start
    assert len(tracer) == 0


def test_obs_imports_no_jax():
    """``bigdl_tpu.obs`` stays stdlib-only: the annotator that needs jax
    is installed from ``bigdl_tpu.utils.profiling``, by the packages that
    import jax anyway."""
    import subprocess
    import sys
    code = ("import sys; import bigdl_tpu.obs; "
            "assert 'jax' not in sys.modules, 'obs imported jax'; "
            "assert bigdl_tpu.obs.default_tracer().annotator is None; "
            "import bigdl_tpu.serving; "
            "assert bigdl_tpu.obs.default_tracer().annotator is not None")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]


# ----------------------------------------------------------------- exporters

def test_metrics_server_endpoints(reg, tracer):
    reg.counter("served_total").inc(2)
    with tracer.span("serve/step"):
        pass
    with obs.MetricsServer(registry=reg, tracer=tracer) as srv:
        text = urllib.request.urlopen(srv.url + "/metrics").read().decode()
        assert "served_total 2" in text
        snap = json.loads(urllib.request.urlopen(
            srv.url + "/metrics.json").read().decode())
        assert snap["metrics"]["served_total"]["series"][0]["value"] == 2
        trace = json.loads(urllib.request.urlopen(
            srv.url + "/trace").read().decode())
        assert any(e.get("name") == "serve/step"
                   for e in trace["traceEvents"])
        index = urllib.request.urlopen(srv.url + "/").read().decode()
        assert "/metrics" in index
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(srv.url + "/nope")


def test_jsonl_sink(tmp_path, reg):
    reg.counter("n_total").inc()
    sink = obs.JsonlSink(str(tmp_path / "m.jsonl"), registry=reg)
    sink.write(step=1)
    reg.counter("n_total").inc()
    sink.write(step=2)
    lines = [json.loads(ln) for ln in
             open(tmp_path / "m.jsonl").read().splitlines()]
    assert [ln["step"] for ln in lines] == [1, 2]
    assert lines[1]["metrics"]["n_total"]["series"][0]["value"] == 2


def test_summary_bridge(reg):
    class Writer:
        def __init__(self):
            self.calls = []

        def add_scalar(self, tag, value, step):
            self.calls.append((tag, value, step))

    reg.counter("steps_total", labels=("loop",)).labels("local").inc(4)
    reg.histogram("lat_seconds", buckets=(1.0,)).observe(0.5)
    w = Writer()
    bridge = obs.SummaryBridge(w, ["steps_total", "lat_seconds"],
                               registry=reg)
    bridge.export(step=7)
    tags = {t: v for t, v, _ in w.calls}
    assert tags['steps_total{loop=local}'] == 4
    assert tags["lat_seconds_count"] == 1
    assert all(s == 7 for _, _, s in w.calls)


# ------------------------------------------------------------------- anomaly

def test_anomaly_detector_flags_slow_steps(reg):
    det = obs.StepTimeAnomalyDetector(loop="t1", k=3.0, window=16,
                                      warmup=4, registry=reg)
    assert not any(det.observe(0.1) for _ in range(8))
    assert det.median() == pytest.approx(0.1)
    assert det.observe(0.5)            # 5x the median
    assert det.observe(0.11) is False  # normal again
    assert det._anomalies.value == 1
    assert det._median.value == pytest.approx(0.1)
    text = reg.prometheus_text()
    assert 'bigdl_step_time_anomalies_total{loop="t1"} 1' in text


def test_anomaly_detector_validates_k(reg):
    with pytest.raises(ValueError, match="k must be > 1"):
        obs.StepTimeAnomalyDetector(loop="t2", k=0.5, registry=reg)


# ---------------------------------------------------------------- demo script

@pytest.mark.slow
def test_obs_demo_script(tmp_path):
    """scripts/obs_demo.sh end to end: train + serve under a live
    endpoint, scraped with curl; Prometheus series from both stacks and
    a Perfetto-loadable trace must come back."""
    import os
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["OBS_DEMO_OUT"] = str(tmp_path / "out")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(["bash", os.path.join(repo, "scripts", "obs_demo.sh")],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "obs demo OK" in r.stdout
    metrics = (tmp_path / "out" / "metrics.txt").read_text()
    assert 'bigdl_train_steps_total{loop="local"}' in metrics
    assert "bigdl_serving_ttft_seconds_bucket" in metrics
    trace = json.loads((tmp_path / "out" / "obs_demo_trace.json").read_text())
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert {"train/dispatch", "serve/step"} <= names
