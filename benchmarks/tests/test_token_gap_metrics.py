"""The readers of the token gap (``harness/gap_account.py`` and the four metrics that use it), on a hand-made ``ctx``: spans as the loop stamps them since PR 36, the engine's counters, the clients' records."""
import json
import os

import pytest

from benchmarks.harness import gap_account

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NEW = ["token_gap_plain_ms", "token_gap_prefill_ms", "token_gaps_after_prefill_share", "deliver_to_client_ms"]
SERVING = ["gpt2m-chat-steady", "gpt2m-longprompt-batch", "lfm2moe-generate-closed", "evabyte-longdoc-generate",
           "dots3-longctx-generate"]


@pytest.fixture(scope="module")
def run():
    import importlib.util
    spec = importlib.util.spec_from_file_location("bench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _iteration(it, t, gap=None, prefills=0, positions=0, step=0.010):
    """The leaves of one iteration that starts at ``t``: 0.2 ms of pick and sweep, a prefill's dispatch of 1 ms where the
    iteration launches one, 1 ms of step dispatch, the readback up to ``t + step``, then the delivery (0.5 ms; ``gap``:
    its ``(gap_ms, gap_streams)``, None for the first of a stretch), 0.1 ms of after. ``prefills`` and ``positions``
    are the DELIVERED block's, which the iteration before dispatched."""
    out = [("serve/pick", t, t + 1e-4, {"iter": it, "queued": 0, "n": 0}), ("serve/sweep", t + 1e-4, t + 2e-4, {"iter": it})]
    d = t + step
    out += [("serve/step.dispatch", t + 2e-4, t + 1.2e-3, {"iter": it}), ("serve/step.readback", t + 1.2e-3, d, {"iter": it}),
            ("serve/step", t + 2e-4, d, {"iter": it, "live": 4})]
    attrs = {"iter": it, "tokens": 4, "retired": 0, "prefills": prefills, "prefill_positions": positions}
    if gap is not None:
        attrs.update(gap_ms=gap[0], gap_streams=gap[1])
    out += [("serve/deliver", d, d + 5e-4, attrs), ("serve/after", d + 5e-4, d + 6e-4, {"iter": it})]
    return out


# deliveries start at 10.010, 10.021, 10.032, 10.073, 10.084: the fourth gap holds one prefill of 2048 positions and 30 ms
SPANS = (_iteration(1, 10.000) + _iteration(2, 10.011, gap=(11.0, 4)) + _iteration(3, 10.022, gap=(11.0, 2))
         + _iteration(4, 10.033, gap=(41.0, 3), prefills=1, positions=2048, step=0.040)
         + _iteration(5, 10.074, gap=(11.0, 1))
         + [("serve/queue_wait", 8.0, 10.03, {"request": 7, "trace": "a", "priority": "standard"})])
# what the parent commit leaves: the same leaves, ``tokens`` and ``retired`` alone on the delivery
OLD_SPANS = [(n, s, e, {k: v for k, v in a.items() if k not in ("gap_ms", "gap_streams", "prefills", "prefill_positions")})
             for n, s, e, a in SPANS]


class _Record:
    def __init__(self, token_at):
        self.token_at = list(token_at)


def _ctx(run, spans=SPANS, counters=None, records=(), traced=(9.99, 10.2)):
    return run.Ctx(spans=list(spans), counters=dict(counters or {}), records=list(records), traced=traced)


def _read(run, name, ctx):
    return run.load_reader(BENCH, name)(ctx)


# ---------------------------------------------------------------- the helper --
def test_gaps_are_split_into_two_classes_and_the_first_delivery_has_none():
    plain = gap_account.gaps(SPANS, after_prefill=False)
    held = gap_account.gaps(SPANS, after_prefill=True)
    assert gap_account.weights(plain) == [(11.0, 4), (11.0, 2), (11.0, 1)]
    assert [g[:4] for g in held] == [(41.0, 3, 1, 2048)]
    assert gap_account.gaps(OLD_SPANS, False) == gap_account.gaps(OLD_SPANS, True) == []


def test_means_and_percentiles_are_weighted_by_streams():
    pairs = [(10.0, 1), (20.0, 3)]
    assert gap_account.weighted_mean(pairs) == pytest.approx(17.5)
    assert gap_account.weighted_percentile(pairs, 25) == 10.0
    assert gap_account.weighted_percentile(pairs, 26) == 20.0
    assert gap_account.weighted_percentile([(5.0, 19), (50.0, 1)], 95) == 5.0
    assert gap_account.weighted_percentile([(5.0, 18), (50.0, 2)], 95) == 50.0
    assert gap_account.weighted_mean([]) is None and gap_account.weighted_percentile([], 95) is None


def test_a_gap_is_tiled_by_the_leaves_between_two_deliveries():
    split = gap_account.by_leaf_ms(SPANS, gap_account.gaps(SPANS, after_prefill=False))
    # deliver 0.5, after 0.1, (0.4 between iterations), pick 0.1, sweep 0.1, dispatch 1.0, readback 8.8
    assert split == pytest.approx({"serve/step.readback": 8.8, "serve/step.dispatch": 1.0, "serve/deliver": 0.5,
                                   "serve/pick": 0.1, "serve/sweep": 0.1, "serve/after": 0.1})
    assert sum(split.values()) == pytest.approx(11.0 - 0.4)
    assert list(split)[0] == "serve/step.readback"
    held = gap_account.by_leaf_ms(SPANS, gap_account.gaps(SPANS, after_prefill=True))
    assert held["serve/step.readback"] == pytest.approx(38.8)
    assert gap_account.by_leaf_ms(SPANS, []) == {}


# --------------------------------------------------------------- the readers --
def test_the_plain_gap_is_the_weighted_mean_of_deliveries_without_a_prefill(run):
    ctx = _ctx(run)
    assert _read(run, "token_gap_plain_ms", ctx) == pytest.approx(11.0)
    note = ctx.notes["token_gap_plain"]
    assert (note["deliveries"], note["gaps"], note["p95_ms"]) == (3, 7, 11.0)
    assert note["oldest_span_late_s"] == pytest.approx(0.01)
    assert note["by_leaf_ms"]["serve/step.readback"] == pytest.approx(8.8)
    assert "ring_overflowed" not in note
    # weights: a slow delivery of one stream beside a fast one of nine
    spans = _iteration(1, 10.0) + _iteration(2, 10.011, gap=(11.0, 9)) + _iteration(3, 10.022, gap=(21.0, 1), step=0.020)
    assert _read(run, "token_gap_plain_ms", _ctx(run, spans)) == pytest.approx(12.0)


def test_the_prefill_gap_is_the_weighted_mean_of_deliveries_after_a_prefill(run):
    spans = SPANS + _iteration(6, 10.085, gap=(71.0, 1), prefills=2, positions=6144, step=0.070)
    ctx = _ctx(run, spans)
    assert _read(run, "token_gap_prefill_ms", ctx) == pytest.approx((41.0 * 3 + 71.0) / 4)
    note = ctx.notes["token_gap_prefill"]
    assert (note["deliveries"], note["gaps"]) == (2, 4)
    assert note["prefill_positions_mean"] == pytest.approx((2048 * 3 + 6144) / 4)
    assert note["by_prefills"] == {"1": {"deliveries": 1, "ms": 41.0}, "2": {"deliveries": 1, "ms": 71.0}}
    # a slice without a prefill has nothing to average
    assert _read(run, "token_gap_prefill_ms", _ctx(run, _iteration(1, 10.0) + _iteration(2, 10.011, gap=(11.0, 4)))) is None


def test_the_share_is_of_the_engines_sums_over_the_whole_run(run):
    ctx = _ctx(run, counters={"token_gaps": 4000, "token_gaps_after_prefill": 700, "dispatches": 9})
    assert _read(run, "token_gaps_after_prefill_share", ctx) == pytest.approx(17.5)
    assert _read(run, "token_gaps_after_prefill_share", _ctx(run, counters={"token_gaps": 50, "token_gaps_after_prefill": 0})) == 0.0
    assert _read(run, "token_gaps_after_prefill_share", _ctx(run, counters={"token_gaps": 0, "token_gaps_after_prefill": 0})) is None
    assert _read(run, "token_gaps_after_prefill_share", _ctx(run, counters={"dispatches": 9})) is None


def test_the_lag_to_the_client_is_from_the_latest_delivery_before_the_token(run):
    # three streams: tokens 0.3, 0.6 and 0.9 ms after the deliveries' starts; a first token counts for nothing, nor does a
    # token outside the traced part or before the slice's first delivery; one token comes 7 ms late, over half a plain gap
    starts = [10.010, 10.021, 10.032, 10.073, 10.084]
    records = [_Record([9.0] + [s + 3e-4 for s in starts]), _Record([10.0105] + [s + 6e-4 for s in starts[1:]]),
               _Record([9.5, 10.005, 10.021 + 9e-4, 10.084 + 7e-3, 10.5])]
    ctx = _ctx(run, records=records)
    lags = sorted(gap_account.client_lags(SPANS, records, ctx.traced))
    assert lags == pytest.approx(sorted([3e-4] * 5 + [6e-4] * 4 + [9e-4, 7e-3]))
    assert _read(run, "deliver_to_client_ms", ctx) == pytest.approx(1e3 * sum(lags) / 11)
    note = ctx.notes["deliver_to_client"]
    assert note["tokens"] == 11 and note["p95_ms"] == pytest.approx(1e3 * (9e-4 + (7e-3 - 9e-4) * 0.5))
    assert note["over_half_a_plain_gap_share"] == pytest.approx(100.0 / 11)
    assert _read(run, "deliver_to_client_ms", _ctx(run, records=[_Record([9.0, 9.5])])) is None


def test_on_the_parents_spans_every_reader_returns_none_and_none_raises(run):
    records = [_Record([10.0, 10.0213, 10.0323])]
    for name in NEW:
        for spans in (OLD_SPANS, []):
            ctx = _ctx(run, spans, counters={"dispatches": 9, "steps_ahead": 4}, records=records)
            assert _read(run, name, ctx) is None
            assert not ctx.notes
    assert _read(run, "token_gap_plain_ms", _ctx(run, traced=None)) is None
    assert _read(run, "deliver_to_client_ms", _ctx(run, records=records, traced=None)) is None


def test_a_ring_that_lost_the_slices_first_spans_is_said_and_not_averaged(run):
    ctx = _ctx(run, traced=(9.7, 10.2))
    assert _read(run, "token_gap_plain_ms", ctx) is None
    note = ctx.notes["token_gap_plain"]
    assert note["ring_overflowed"] is True and note["oldest_span_late_s"] == pytest.approx(0.3)
    assert note["deliveries"] == 3
    # a request's long wait in the queue, recorded when it ended, does not hide it
    assert min(s[1] for s in SPANS) < 9.7
    assert gap_account.ring_late_s(SPANS, (9.76, 10.2)) == pytest.approx(0.24)
    assert _read(run, "token_gap_plain_ms", _ctx(run, traced=(9.76, 10.2))) == pytest.approx(11.0)


# -------------------------------------------------------------- the manifest --
def test_every_new_metric_is_an_entry_and_a_file(run):
    """By presence, not by place: a later PR appends after these."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert len(entries) == len(manifest["per_layer"])
    layers = {"token_gap_plain_ms": "Scheduler", "token_gap_prefill_ms": "Cache manager and model forward",
              "token_gaps_after_prefill_share": "Scheduler", "deliver_to_client_ms": "Engine facade"}
    for name in NEW:
        m = entries[name]
        assert os.path.isfile(os.path.join(BENCH, "metrics", name + ".py"))
        assert callable(run.load_reader(BENCH, name))
        assert (m["moves"], m["better"], m["layer"]) == ("tpot_p95_ms", "lower", layers[name])
        assert m["unit"] == ("%" if name.endswith("_share") else "ms")
        assert m["source"] == ("program_counter" if name.endswith("_share") else "program_span")
        assert m["workloads"] == (SERVING[:3] if name == "token_gap_prefill_ms" else SERVING)
    cells = {c["name"]: c for c in manifest["workloads"]}
    for cell in SERVING:
        reported = {m["name"] for m in run.metrics_for(manifest, cells[cell], "per_layer")}
        assert set(NEW) - reported == (set() if cell in SERVING[:3] else {"token_gap_prefill_ms"})
    assert not set(NEW) & {m["name"] for m in run.metrics_for(manifest, cells["resnet50-train-1chip"], "per_layer")}
