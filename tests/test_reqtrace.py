"""Unit tests for the request-tracing layer (ISSUE 20): per-request
timeline rings + Perfetto export (one synthetic track per request),
histogram exemplars as the metrics->timeline join, the ``/requests``
and ``/healthz`` endpoints, the flight-recorder dump paths, the flag-off
no-op contract, and that the flag does not decide how a serving
executable is called.

Recorder/flight tests run against FRESH ``ReqTraceRecorder`` /
``FlightRecorder`` instances (never the process globals) so they stay
independent of whatever instrumented serving code ran earlier in the
pytest process; endpoint tests pass those instances into the server
explicitly for the same reason.
"""

import contextlib
import json
import re
import urllib.error
import urllib.request

import pytest

from bigdl_tpu import obs
from bigdl_tpu.obs import reqtrace
from bigdl_tpu.obs.metrics import MetricsRegistry
from bigdl_tpu.obs.reqtrace import FlightRecorder, ReqTraceRecorder


@pytest.fixture
def rec():
    return ReqTraceRecorder(capacity=32, max_traces=16)


@pytest.fixture
def reg():
    return MetricsRegistry()


def _get(url):
    with urllib.request.urlopen(url) as r:
        return r.status, json.loads(r.read().decode())


# ------------------------------------------------------------------ recorder

def test_mint_is_unique_hex():
    ids = {reqtrace.mint() for _ in range(64)}
    assert len(ids) == 64
    assert all(re.fullmatch(r"[0-9a-f]{16}", t) for t in ids)


def test_event_timeline_roundtrip(rec):
    tr = reqtrace.mint()
    rec.event(tr, "submit", request=7, engine="e0", prompt_tokens=5)
    rec.event(tr, "tokens", request=7, engine="e0", off=0, n=4)
    rec.event(tr, "retire", request=7, engine="e0", tokens=4)
    tl = rec.timeline(tr)
    assert tl["trace"] == tr
    assert tl["request"] == 7          # captured off the first event
    assert tl["dropped"] == 0
    assert [e["event"] for e in tl["events"]] == ["submit", "tokens",
                                                  "retire"]
    assert tl["events"][0]["prompt_tokens"] == 5
    assert tl["events"][1]["off"] == 0 and tl["events"][1]["n"] == 4
    ts = [e["t"] for e in tl["events"]]
    assert ts == sorted(ts)
    # unknown trace: None, never a synthesized empty timeline
    assert rec.timeline("no-such-trace") is None
    snap = rec.snapshot()
    assert snap[tr]["first"] == "submit"
    assert snap[tr]["last"] == "retire"
    assert snap[tr]["events"] == 3
    assert snap[tr]["request"] == 7
    assert snap[tr]["end"] >= snap[tr]["start"]


def test_per_trace_ring_bounds_and_counts_drops():
    rec = ReqTraceRecorder(capacity=4, max_traces=8)
    tr = reqtrace.mint()
    for i in range(10):
        rec.event(tr, f"e{i}", i=i)
    tl = rec.timeline(tr)
    assert [e["event"] for e in tl["events"]] == ["e6", "e7", "e8", "e9"]
    assert tl["dropped"] == 6


def test_trace_lru_eviction_keeps_recently_touched():
    rec = ReqTraceRecorder(capacity=4, max_traces=3)
    for tr in ("t1", "t2", "t3"):
        rec.event(tr, "submit")
    rec.event("t1", "tokens")          # touch t1: now t2 is oldest
    rec.event("t4", "submit")          # evicts t2
    assert len(rec) == 3
    assert set(rec.traces()) == {"t1", "t3", "t4"}
    assert rec.timeline("t2") is None


def test_perfetto_one_track_per_request(rec):
    done, open_ = reqtrace.mint(), reqtrace.mint()
    rec.event(done, "submit", request=1)
    rec.event(done, "retire", request=1)
    rec.event(open_, "submit", request=2)
    rec.event(open_, "tokens", request=2, off=0, n=4)
    doc = rec.perfetto()
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    instants = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    names = [m["args"]["name"] for m in metas
             if m["name"] == "thread_name"]
    assert f"req 1 [{done}]" in names and f"req 2 [{open_}]" in names
    # distinct synthetic tids: one track per request
    tids = {m["tid"] for m in metas if m["name"] == "thread_name"}
    assert len(tids) == 2
    by_trace = {s["args"]["trace"]: s for s in slices}
    assert by_trace[done]["name"] == "lifetime"          # closed: retired
    assert by_trace[open_]["name"] == "lifetime (open)"  # still in flight
    assert all(s["dur"] >= 1.0 for s in slices)
    assert len(instants) == 4                            # one per event
    assert any(m["name"] == "process_name" for m in metas)
    # narrowing to one trace drops the other track entirely
    one = rec.perfetto(done)
    assert {e["args"]["trace"] for e in one["traceEvents"]
            if e["ph"] == "X"} == {done}
    # unknown trace: no slices (the endpoint turns this into a 404)
    none = rec.perfetto("no-such-trace")
    assert not any(e["ph"] == "X" for e in none["traceEvents"])


def test_flag_off_records_nothing(rec):
    prev = reqtrace.set_enabled(False)
    try:
        assert not reqtrace.enabled()
        rec.event(reqtrace.mint(), "submit", request=1)
        assert len(rec) == 0
        fl = FlightRecorder(iterations=4)
        fl.note_iteration("e0", live=1)
        fl.note_event("e0", "preempt")
        assert fl.snapshot() == {}
        assert fl.dump("off", recorder=rec, force=True) is None
    finally:
        reqtrace.set_enabled(prev)
    # the global obs kill switch vetoes tracing too
    prev_obs = obs.set_enabled(False)
    try:
        assert not reqtrace.enabled()
        rec.event(reqtrace.mint(), "submit")
        assert len(rec) == 0
    finally:
        obs.set_enabled(prev_obs)
    # None trace ids (flag-off submits) are always a no-op
    rec.event(None, "submit", request=1)
    assert len(rec) == 0


# ------------------------------------------------------------------- flight

def test_flight_recorder_rings_and_dump(tmp_path, rec):
    fl = FlightRecorder(iterations=4, directory=str(tmp_path),
                        min_interval_s=60.0)
    for i in range(6):
        fl.note_iteration("e0", live=i, queued=0, step_s=0.01)
    fl.note_event("e0", "preempt", request=3, delivered=8)
    fl.note_iteration("e1", live=1)
    snap = fl.snapshot()
    assert len(snap["e0"]) == 4                    # bounded per engine
    assert snap["e0"][-1]["event"] == "preempt"
    assert all("t" in r for r in snap["e0"])
    tr = reqtrace.mint()
    rec.event(tr, "submit", request=9)
    path = fl.dump("step-time anomaly: 12x median", recorder=rec)
    assert path is not None and path.startswith(str(tmp_path))
    assert re.fullmatch(r"flight-[\d.]+-[A-Za-z0-9-]+\.json",
                        path.rsplit("/", 1)[-1])
    doc = json.load(open(path))
    assert set(doc) == {"time", "reason", "iterations", "requests"}
    assert doc["reason"] == "step-time anomaly: 12x median"
    assert len(doc["iterations"]["e0"]) == 4
    assert doc["requests"][tr]["events"][0]["event"] == "submit"
    # anomaly storms are rate-limited to one artifact...
    assert fl.dump("again", recorder=rec) is None
    # ...unless forced (SIGUSR2 / operator ask)
    assert fl.dump("forced", recorder=rec, force=True) is not None
    assert fl.dumps == 2


def test_flight_dump_survives_unwritable_dir(rec):
    fl = FlightRecorder(directory="/dev/null/nope", min_interval_s=0.0)
    # a full/bogus disk must not fail serving: None, no raise
    assert fl.dump("x", recorder=rec, force=True) is None
    assert fl.dumps == 0


# ---------------------------------------------------------------- exemplars

def test_histogram_exemplars_worst_recent(reg):
    h = reg.histogram("ttft_seconds", buckets=(0.5, 1.0))
    h.observe(0.7, exemplar="trace-slow")
    h.observe(0.6, exemplar="trace-slower?")       # smaller: kept out
    h.observe(9.0, exemplar="trace-worst")
    h.observe(0.2)                                 # no exemplar: fine
    exes = h.exemplars()
    assert exes["1"]["trace"] == "trace-slow"      # worst recent in le=1
    assert exes["+Inf"]["trace"] == "trace-worst"
    assert exes["1"]["value"] == pytest.approx(0.7)
    assert "0.5" not in exes                       # no exemplar observed
    # surfaced through the JSON snapshot, next to the series...
    entry = reg.snapshot()["ttft_seconds"]["series"][0]
    assert entry["exemplars"]["+Inf"]["trace"] == "trace-worst"
    # ...but the Prometheus text page stays byte-identical
    bare = MetricsRegistry()
    b = bare.histogram("ttft_seconds", buckets=(0.5, 1.0))
    for v in (0.7, 0.6, 9.0, 0.2):
        b.observe(v)
    assert reg.prometheus_text() == bare.prometheus_text()
    # histograms without exemplars don't grow an empty key
    g = reg.histogram("plain_seconds", buckets=(1.0,))
    g.observe(0.5)
    assert "exemplars" not in reg.snapshot()["plain_seconds"]["series"][0]


# ---------------------------------------------------------------- endpoints

def test_requests_endpoint(reg, rec):
    tr = reqtrace.mint()
    rec.event(tr, "submit", request=4, engine="e0")
    rec.event(tr, "retire", request=4, engine="e0")
    with obs.MetricsServer(registry=reg, recorder=rec) as srv:
        status, index = _get(srv.url + "/requests")
        assert status == 200
        assert index["requests"][tr]["last"] == "retire"
        status, tl = _get(f"{srv.url}/requests?trace={tr}")
        assert status == 200
        assert [e["event"] for e in tl["events"]] == ["submit", "retire"]
        status, doc = _get(f"{srv.url}/requests?trace={tr}&fmt=perfetto")
        assert status == 200
        assert any(e["ph"] == "X" for e in doc["traceEvents"])
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(srv.url + "/requests?trace=bogus")
        assert e.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(
                srv.url + "/requests?trace=bogus&fmt=perfetto")
        assert e.value.code == 404
        index_page = urllib.request.urlopen(srv.url + "/").read().decode()
        assert "/requests" in index_page and "/healthz" in index_page


def test_healthz_endpoint(reg, rec):
    state = {"engine:e0": True, "fleet:f0:replica:0": True}
    alive = {"on": True}

    def probe():
        return dict(state) if alive["on"] else None

    reg.register_probe(probe)
    with obs.MetricsServer(registry=reg, recorder=rec) as srv:
        status, doc = _get(srv.url + "/healthz")
        assert status == 200
        assert doc == {"healthy": True, "components": state}
        state["fleet:f0:replica:0"] = False       # ejected replica
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(srv.url + "/healthz")
        assert e.value.code == 503
        doc = json.loads(e.value.read().decode())
        assert doc["healthy"] is False
        assert doc["components"]["fleet:f0:replica:0"] is False
        # a probe returning None self-unregisters (closed engine)
        alive["on"] = False
        status, doc = _get(srv.url + "/healthz")
        assert status == 200 and doc["components"] == {}
        assert probe not in reg._probes


def test_healthz_probe_exception_is_unhealthy_not_fatal(reg, rec):
    def bad():
        raise RuntimeError("mid-rebuild")

    reg.register_probe(bad)
    try:
        with obs.MetricsServer(registry=reg, recorder=rec) as srv:
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(srv.url + "/healthz")
            assert e.value.code == 503
    finally:
        reg.unregister_probe(bad)


def test_profile_endpoint_validates_and_serializes(reg, rec):
    with obs.MetricsServer(registry=reg, recorder=rec) as srv:
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(srv.url + "/profile?seconds=banana")
        assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(srv.url + "/profile?seconds=-1")
        assert e.value.code == 400


# ------------------------------------------- how executables are called

_PROMPTS = [[5, 9, 2, 17, 3], [1, 1, 4, 60, 8], [7, 3, 3]]


def _tiny_engine(**kw):
    import jax
    from bigdl_tpu.models.gpt import GPTForCausalLM
    from bigdl_tpu.serving import ServingEngine

    m = GPTForCausalLM(vocab_size=61, hidden_size=32, n_layers=2,
                       n_heads=4, max_position=64)
    params, _ = m.setup(jax.random.PRNGKey(3), None)
    return ServingEngine(m, params, max_slots=2, max_queue=8, **kw)


@contextlib.contextmanager
def _request_tracing(value):
    prev = reqtrace.set_enabled(value)
    try:
        yield
    finally:
        reqtrace.set_enabled(prev)


def _serve(tracing, **kw):
    """(type of each held executable, tokens, trace counts) of one
    engine built and driven with request tracing ``tracing``."""
    with _request_tracing(tracing), _tiny_engine(**kw) as engine:
        assert reqtrace.enabled() is tracing
        held = {n: getattr(engine.slots, n)
                for n in ("_prefill_fn", "_step_fn", "_copy_fn")
                if hasattr(engine.slots, n)}
        # what ``jax.jit`` returned: it lowers, and nothing stands
        # between the loop and its lazy call
        assert all(hasattr(f, "lower") for f in held.values()), held
        tokens = [list(engine.result(engine.submit(p, 6), timeout=120))
                  for p in _PROMPTS]
        traces = {k: v for k, v in engine.stats.items()
                  if k.endswith("_traces")}
    return {n: type(f) for n, f in held.items()}, tokens, traces


@pytest.mark.parametrize("kw", [{}, {"paged": True}],
                         ids=["dense", "paged"])
def test_request_tracing_does_not_change_how_executables_are_called(kw):
    on, off = _serve(True, **kw), _serve(False, **kw)
    assert set(on[0]) >= {"_prefill_fn", "_step_fn"}
    assert ("_copy_fn" in on[0]) == bool(kw)
    assert on == off


def test_metrics_exposition_has_decode_counters_and_no_cost_gauges():
    with _request_tracing(True), _tiny_engine() as engine, \
            obs.MetricsServer(port=0) as srv:
        engine.result(engine.submit(_PROMPTS[0], 4), timeout=120)
        with urllib.request.urlopen(srv.url + "/metrics") as r:
            text = r.read().decode()
    families = {ln.split("{")[0].split(" ")[0]
                for ln in text.splitlines() if ln and not ln.startswith("#")}
    assert {"bigdl_decode_traces", "bigdl_decode_dispatches"} <= families
    # no utilisation, FLOP/s or bandwidth gauge: the benchmark counts those
    # from shapes (docs/observability.md)
    assert not [f for f in families
                if re.search(r"mfu|flops|hbm_bytes", f)]
