"""The readers of the program's spans (``harness/span_account.py`` and the six metrics that use it), on hand-made spans and on a small recorded trace of their own (``span_trace.json``), on the CPU."""
import json
import os

import pytest

from benchmarks.harness import span_account, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NEW = ["queue_wait_ms.steady", "admit_to_first_token_ms.steady", "step_host_ms", "idle_named_share.serve",
       "prefill_useful_share.steady", "prefill_useful_share.batch"]


def _run_module():
    import importlib.util
    spec = importlib.util.spec_from_file_location("bench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def run():
    return _run_module()


@pytest.fixture()
def trace():
    return trace_reduce.Reduced.from_json(os.path.join(HERE, "span_trace.json"))


def _iteration(it, t, prefill=None):
    """The spans one loop iteration leaves in ``ctx.spans``, from ``t`` on: 0.1 ms of pick, 0.05 of sweep, a prefill
    (``prefill``: its attributes) of 0.4 ms pack and 2 ms dispatch, a step of 3 ms dispatch and 30 ms readback, 0.5 ms of
    deliver, 0.2 of after."""
    out = [("serve/pick", t, t + 1e-4, {"iter": it, "queued": 1, "n": 1}), ("serve/sweep", t + 1e-4, t + 1.5e-4, {"iter": it})]
    t += 2e-4
    if prefill is not None:
        out += [("serve/prefill.pack", t, t + 4e-4, {"iter": it}), ("serve/prefill.dispatch", t + 4e-4, t + 2.4e-3, {"iter": it}),
                ("serve/prefill", t, t + 2.5e-3, dict(prefill, iter=it))]
        t += 2.5e-3
    out += [("serve/step.dispatch", t, t + 3e-3, {"iter": it}), ("serve/step.readback", t + 3e-3, t + 33e-3, {"iter": it}),
            ("serve/step", t, t + 33e-3, {"iter": it, "live": 7}),
            ("serve/deliver", t + 33e-3, t + 33.5e-3, {"iter": it, "tokens": 7, "retired": 0}),
            ("serve/after", t + 33.5e-3, t + 33.7e-3, {"iter": it})]
    return out


SPANS = (_iteration(1, 10.0, {"n": 2, "rows": 4, "bucket": 128, "tokens": 150, "requests": [0, 1]}) + _iteration(2, 10.05)
         + [("serve/idle", 10.09, 10.19, {"iter": 3})]
         + _iteration(3, 10.19, {"n": 1, "rows": 4, "bucket": 512, "tokens": 362, "requests": [2]})
         + [("serve/queue_wait", 9.98, 10.0, {"request": 0, "trace": "a", "priority": "standard"}),
            ("serve/queue_wait", 9.99, 10.0, {"request": 1, "trace": "b", "priority": "standard"}),
            ("serve/queue_wait", 10.16, 10.19, {"request": 2, "trace": "c", "priority": "standard"}),
            ("serve/first_token", 10.0, 10.04, {"request": 0, "trace": "a", "prompt_tokens": 100, "bucket": 128}),
            ("serve/first_token", 10.0, 10.04, {"request": 1, "trace": "b", "prompt_tokens": 50, "bucket": 128}),
            ("serve/first_token", 10.19, 10.26, {"request": 2, "trace": "c", "prompt_tokens": 362, "bucket": 512}),
            ("serve/submit", 9.98, 9.9801, {"request": 0, "trace": "a", "engine": "0"})])
# what a commit without this PR's spans leaves: three names, ``live`` and ``n``
OLD_SPANS = [("serve/submit", 9.98, 9.9801, {"request": 0, "engine": "0"}), ("serve/prefill", 10.0, 10.0025, {"n": 2}),
             ("serve/step", 10.0025, 10.0355, {"live": 7})]


class _Record:
    def __init__(self, due_at, first=None, error=None):
        self.due_at, self.error = due_at, error
        self.token_at = [] if first is None else [first, first + 0.05]


def _ctx(run, spans=(), trace=None, records=(), traced=(9.9, 10.3)):
    return run.Ctx(spans=list(spans), trace=trace, records=list(records), traced=traced)


# ---------------------------------------------------------------- the helper --
def test_leaves_are_the_loop_spans_that_no_other_name_extends():
    names = {s[0] for s in span_account.loop_leaves(SPANS)}
    assert names == {"serve/idle", "serve/pick", "serve/sweep", "serve/prefill.pack", "serve/prefill.dispatch",
                     "serve/step.dispatch", "serve/step.readback", "serve/deliver", "serve/after"}
    assert span_account.loop_leaves(OLD_SPANS) == []


def test_host_ms_per_step_leaves_out_the_two_waits():
    ms, by_leaf = span_account.host_ms_per_step(SPANS)
    # three steps; two prefills of 0.4 + 2.0 ms; every iteration 0.1 + 0.05 + 3 + 0.5 + 0.2 ms
    assert by_leaf == pytest.approx({"serve/pick": 0.1, "serve/sweep": 0.05, "serve/prefill.pack": 0.8 / 3,
                                     "serve/prefill.dispatch": 4.0 / 3, "serve/step.dispatch": 3.0, "serve/deliver": 0.5,
                                     "serve/after": 0.2})
    assert ms == pytest.approx(3.85 + 4.8 / 3)
    assert span_account.host_ms_per_step(OLD_SPANS) == (None, {})
    assert span_account.host_ms_per_step([]) == (None, {})


def test_gaps_are_cut_as_the_harness_cuts_them(trace):
    gaps = span_account.device_gaps(trace)
    assert gaps == pytest.approx([(0.010, 0.014), (0.024, 0.030), (0.040, 0.042), (0.050, 0.051)])
    assert sum(b - a for a, b in gaps) == pytest.approx(sum(s for _, s in trace_reduce.idle_gaps(trace)))
    assert span_account.device_gaps(trace_reduce.Reduced({}, {}, [])) == []


def test_idle_seconds_lie_under_the_leaves_that_overlap_the_gap(trace):
    named_s, by_leaf, unnamed = span_account.idle_by_leaf(trace)
    # gap 1 (4 ms) under one leaf (and the runtime event inside it): 3.7 ms of it, the rest before the leaf starts;
    # gap 2 (6 ms) under two: readback covers 1.5 ms of it, deliver 3.9 ms
    assert named_s == pytest.approx(0.010)
    assert by_leaf == pytest.approx({"serve/step.dispatch": 0.0035, "serve/step.readback": 0.0015, "serve/deliver": 0.0039})
    # gap 3 under no leaf, but a runtime event of another thread; gap 4 under nothing
    assert unnamed == [["bench-client-3", "np.asarray(jax.Array)", pytest.approx(0.002)], [None, None, pytest.approx(0.001)]]


def test_the_harness_labels_a_gap_by_the_leaf_too(trace):
    """A leaf starts before the runtime event it wraps, so ``trace_reduce.idle_gaps`` (not edited) names gap 1 by it."""
    labels = dict(trace_reduce.idle_gaps(trace))
    assert labels["before jit_step | host: serve/step.dispatch"] == pytest.approx(0.004)
    assert labels["before jit_prefill | host: serve/deliver"] == pytest.approx(0.006)


def test_a_trace_without_the_programs_leaves_gives_nothing(trace):
    assert span_account.idle_by_leaf(trace_reduce.Reduced({}, {}, [])) is None
    bare = trace_reduce.Reduced(trace.ops, trace.modules, [h for h in trace.host if not h[1].startswith("serve/")])
    assert span_account.idle_by_leaf(bare) is None


# --------------------------------------------------------------- the readers --
def test_every_new_metric_is_an_entry_and_a_file(run):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"]][-len(NEW):] == NEW       # appended, in the issue's order
    reports = {c["name"]: {m["name"] for m in run.metrics_for(manifest, c, "per_layer")} for c in manifest["workloads"]}
    for name in NEW:
        assert callable(run.load_reader(BENCH, name))
        for cell in entries[name]["workloads"]:
            assert name in reports[cell]
            e2e = {m["name"] for m in run.metrics_for(manifest, next(c for c in manifest["workloads"] if c["name"] == cell),
                                                      "end_to_end")}
            assert entries[name]["moves"] in e2e
    assert not set(NEW) & reports["resnet50-train-1chip"]


def test_the_ttft_split_and_the_clients_side(run):
    records = [_Record(9.97, first=10.05), _Record(9.985, first=10.045), _Record(10.15, first=10.27),
               _Record(9.5, first=9.6), _Record(10.2), _Record(10.21, first=10.3, error=RuntimeError("refused"))]
    ctx = _ctx(run, SPANS, records=records)
    assert run.load_reader(BENCH, "queue_wait_ms.steady")(ctx) == pytest.approx((20 + 10 + 30) / 3)
    assert run.load_reader(BENCH, "admit_to_first_token_ms.steady")(ctx) == pytest.approx((40 + 40 + 70) / 3)
    assert ctx.notes["queue_wait_spans"] == ctx.notes["first_token_spans"] == 3
    client = ctx.notes["client_first_token_ms_due_in_traced"]
    assert client["requests"] == 3 and client["mean"] == pytest.approx((80 + 60 + 120) / 3)


def test_prefill_useful_share_is_tokens_over_rows_times_bucket(run):
    for name in ("prefill_useful_share.steady", "prefill_useful_share.batch"):
        assert run.load_reader(BENCH, name)(_ctx(run, SPANS)) == pytest.approx(100.0 * (150 + 362) / (4 * 128 + 4 * 512))


def test_step_host_ms_and_its_notes(run):
    ctx = _ctx(run, SPANS)
    assert run.load_reader(BENCH, "step_host_ms")(ctx) == pytest.approx(3.85 + 4.8 / 3)
    assert list(ctx.notes["step_host_ms_by_leaf"])[0] == "serve/step.dispatch"          # largest first


def test_idle_named_share_and_its_notes(run, trace):
    ctx = _ctx(run, SPANS, trace=trace)
    assert run.load_reader(BENCH, "idle_named_share.serve")(ctx) == pytest.approx(100.0 * 10 / 13)
    assert list(ctx.notes["idle_s_by_leaf"]) == ["serve/deliver", "serve/step.dispatch", "serve/step.readback"]
    assert ctx.notes["idle_s_between_leaves"] == pytest.approx(0.010 - 0.0089)
    assert ctx.notes["idle_s_under_no_leaf"][0][:2] == ["bench-client-3", "np.asarray(jax.Array)"]
    json.dumps(ctx.notes)                                                               # the result line prints them


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("what", ["no span, no trace", "a commit without these spans", "an empty trace"])
def test_where_there_is_nothing_to_read_a_reader_returns_none(run, trace, name, what):
    if what == "no span, no trace":
        ctx = _ctx(run, traced=None)
    elif what == "a commit without these spans":       # serve/prefill without rows, bucket, tokens; no leaf in the trace
        bare = trace_reduce.Reduced(trace.ops, trace.modules, [h for h in trace.host if not h[1].startswith("serve/")])
        ctx = _ctx(run, OLD_SPANS, trace=bare, records=[_Record(10.0, first=10.1)])
    else:
        ctx = _ctx(run, OLD_SPANS, trace=trace_reduce.Reduced({}, {}, []))
    assert run.load_reader(BENCH, name)(ctx) is None
    json.dumps(ctx.notes)
