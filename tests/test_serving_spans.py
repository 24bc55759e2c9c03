"""The serving loop named phase by phase (docs/observability.md).

A tiny engine on the CPU serves a dozen requests; what is held: every
leaf of one loop iteration carries that iteration's ``iter``, every
request gets exactly one ``serve/queue_wait`` and one ``serve/first_token``
that meet at its admission, a ``serve/prefill`` counts no more prompt
tokens than positions it computed, an iteration opens at most 12 spans,
and under a real profiler session the leaves (and no parent) lie on the
``/host:CPU`` plane. And the token gap: every ``serve/deliver`` says how
long the streams it serves waited since the delivery before
(``gap_ms``, ``gap_streams``) and how many prefills the device had queued
before its block (``prefills``, ``prefill_positions``), the same numbers
reach ``bigdl_serving_token_gap_seconds`` and ``engine.stats``, and the
kill switch stops all three.
"""

import collections
import glob
import os
import time

import jax
import pytest

from bigdl_tpu import obs
from bigdl_tpu.models.gpt import GPTForCausalLM
from bigdl_tpu.obs.metrics import HistogramChild
from bigdl_tpu.resilience import faults
from bigdl_tpu.serving import ServingEngine

PARENTS = {"serve/step", "serve/prefill"}
LEAVES = {"serve/idle", "serve/pick", "serve/sweep", "serve/prefill.pack",
          "serve/prefill.dispatch", "serve/step.dispatch",
          "serve/step.readback", "serve/deliver", "serve/after"}
PROMPTS = [[5, 9, 2, 17, 3], [1, 1, 4, 60, 8], [7, 3, 3],
           [9, 9, 9, 1, 0, 2, 4], [2, 4], [11, 12, 13, 14, 15, 16],
           list(range(1, 20)), [3] * 9, [8, 7, 6, 5], [21, 22],
           list(range(30, 47)), [4, 4, 4, 4, 4, 4, 4, 4]]


def _dispatched(spans, loop=None):
    """The attributes of the ``serve/step`` spans that dispatched a block
    (``live`` and what else describes it); the span that only drains the
    last block of a busy stretch carries none of them."""
    return [s.attrs for s in spans if s.name == "serve/step"
            and "live" in s.attrs and loop in (None, s.thread_id)]


def _engine():
    model = GPTForCausalLM(vocab_size=61, hidden_size=32, n_layers=2,
                           n_heads=4, max_position=64)
    params, _ = model.setup(jax.random.PRNGKey(3), None)
    return ServingEngine(model, params, max_slots=4)


@pytest.fixture(scope="module")
def served():
    """``(handles, spans)``: a dozen requests through four slots, and the
    spans the engine's loop thread put into the default ring."""
    tracer = obs.default_tracer()
    tracer.clear()
    with _engine() as engine:
        loop = engine.scheduler._thread.ident
        handles = [engine.submit(p, 3 + i % 5)
                   for i, p in enumerate(PROMPTS)]
        for h in handles:
            h.result(timeout=120)
    return handles, [s for s in tracer.spans() if s.thread_id == loop]


def test_every_span_of_the_loop_has_a_known_name_and_an_iter(served):
    _, spans = served
    on_loop = [s for s in spans if "iter" in s.attrs]
    assert {s.name for s in on_loop} == PARENTS | LEAVES
    assert {s.name for s in spans if "iter" not in s.attrs} == {
        "serve/queue_wait", "serve/first_token"}
    assert all(s.parent is None for s in on_loop if s.name in PARENTS)


def test_the_leaves_of_one_iteration_share_its_iter(served):
    """Within a parent, each leaf carries the parent's ``iter``; and the
    iterations follow one another on the clock in the order of ``iter``."""
    _, spans = served
    parents = [s for s in spans if s.name in PARENTS]
    leaves = [s for s in spans if "." in s.name]
    assert leaves and all(l.parent in PARENTS for l in leaves)
    for l in leaves:
        (p,) = [p for p in parents if p.name == l.parent
                and p.start <= l.start and l.end <= p.end]
        assert l.attrs["iter"] == p.attrs["iter"]
    by_iter = collections.defaultdict(list)
    for s in spans:
        if "iter" in s.attrs:
            by_iter[s.attrs["iter"]].append(s)
    order = sorted(by_iter)
    for a, b in zip(order, order[1:]):
        assert max(s.end for s in by_iter[a]) <= min(
            s.start for s in by_iter[b])


def test_an_iteration_opens_at_most_twelve_spans(served):
    _, spans = served
    per_iter = collections.Counter(s.attrs["iter"] for s in spans
                                   if "iter" in s.attrs)
    assert per_iter and max(per_iter.values()) <= 12
    # and a decoding iteration opens each of its phases once: it
    # dispatches a block where a stream is live and reads back and
    # delivers the block that was in flight, which the first iteration of
    # a busy stretch has none of and the last has alone
    steps = [s for s in spans if s.name == "serve/step"]
    kinds = collections.Counter()
    for step in steps:
        it = step.attrs["iter"]
        names = [s.name for s in spans if s.attrs.get("iter") == it]
        assert len(names) == len(set(names)), names
        assert {"serve/pick", "serve/sweep", "serve/step",
                "serve/after"} <= set(names)
        dispatched = "live" in step.attrs
        read = dispatched and step.attrs["ahead"] == 1 or not dispatched
        assert ("serve/step.dispatch" in names) == dispatched
        assert ("serve/step.readback" in names) == read
        assert ("serve/deliver" in names) == read
        kinds[dispatched, read] += 1
    assert kinds[True, False] >= 1 and kinds[False, True] >= 1
    assert kinds[True, True] > kinds[True, False] + kinds[False, True]


def test_each_request_waits_once_and_meets_its_first_token(served):
    handles, spans = served
    for h in handles:
        (wait,) = [s for s in spans if s.name == "serve/queue_wait"
                   and s.attrs["request"] == h.id]
        (first,) = [s for s in spans if s.name == "serve/first_token"
                    and s.attrs["request"] == h.id]
        assert wait.attrs["trace"] == first.attrs["trace"] == h.trace
        assert h.trace is not None
        assert wait.attrs["priority"] == "standard"
        assert wait.end == first.start            # one clock read: the pop
        assert wait.duration == pytest.approx(h.admitted_at
                                              - h.submitted_at)
        assert first.duration == pytest.approx(h.first_token_at
                                               - h.admitted_at)
        assert first.attrs["prompt_tokens"] == h.prompt.size
        assert first.attrs["bucket"] >= h.prompt.size
    # the request timeline's ``admit`` event carries the same wait
    h = handles[0]
    (admit,) = [e for e in obs.default_recorder().timeline(h.trace)["events"]
                if e["event"] == "admit"]
    assert admit["queue_wait_s"] == pytest.approx(h.admitted_at
                                                  - h.submitted_at)


def test_a_prefill_counts_what_it_asked_for_and_what_it_computed(served):
    handles, spans = served
    prefills = [s for s in spans if s.name == "serve/prefill"]
    admitted = [r for s in prefills for r in s.attrs["requests"]]
    assert sorted(admitted) == sorted(h.id for h in handles)
    sizes = {h.id: h.prompt.size for h in handles}
    for s in prefills:
        a = s.attrs
        assert a["n"] == len(a["requests"]) <= a["rows"]
        assert a["tokens"] == sum(sizes[r] for r in a["requests"])
        assert a["tokens"] <= a["rows"] * a["bucket"]
    picks = [s for s in spans if s.name == "serve/pick"]
    assert sum(s.attrs["n"] for s in picks) == len(handles)
    assert all(s.attrs["n"] <= s.attrs["queued"] for s in picks)
    delivers = [s for s in spans if s.name == "serve/deliver"]
    assert sum(s.attrs["tokens"] for s in delivers) == sum(
        len(h.tokens) for h in handles)
    assert sum(s.attrs["retired"] for s in delivers) == len(handles)


def test_a_step_says_how_its_attention_read_and_how_much(served):
    """Off the chip the read is masked, which is the whole table: four
    slots of one block of 128 positions each (64 positions, rounded up)."""
    _, spans = served
    steps = _dispatched(spans)
    assert steps
    for a in steps:
        assert a["attn_read"] == "masked"
        assert a["attn_blocks"] == a["attn_blocks_table"] == 4


def test_a_step_says_how_it_samples_and_how_many_streams_it_draws_for(
        served, monkeypatch):
    """Off the chip the sampled branch sorts, and a greedy engine never
    takes it: ``sampled`` 0 on every step and prefill. Then, the table's
    word overridden (no CPU table selects the kernel), two sampled
    requests beside a greedy one: ``sampled`` counts the live slots with a
    temperature, falls as they retire, and the admissions count theirs."""
    from bigdl_tpu.ops import sampling
    _, spans = served
    steps = _dispatched(spans)
    assert {a["sampler"] for a in steps} == {"sort"}
    assert {a["sampled"] for a in steps} == {0}
    assert {s.attrs["sampled"] for s in spans
            if s.name == "serve/prefill"} == {0}
    monkeypatch.setattr(sampling, "applies", lambda *a: True)
    tracer = obs.default_tracer()
    tracer.clear()
    model = GPTForCausalLM(vocab_size=61, hidden_size=32, n_layers=2,
                           n_heads=4, max_position=64)
    params, _ = model.setup(jax.random.PRNGKey(3), None)
    with ServingEngine(model, params, max_slots=4, top_k=10,
                       top_p=0.9) as engine:
        loop = engine.scheduler._thread.ident
        assert engine.slots.sampler == "kernel"
        handles = [engine.submit(PROMPTS[0], 8),
                   engine.submit(PROMPTS[1], 3, temperature=0.8),
                   engine.submit(PROMPTS[2], 6, temperature=1.2)]
        for h in handles:
            h.result(timeout=300)
    spans = [s for s in tracer.spans() if s.thread_id == loop]
    steps = _dispatched(spans)
    assert {a["sampler"] for a in steps} == {"kernel"}
    assert all(0 <= a["sampled"] <= min(2, a["live"]) for a in steps)
    assert {a["sampled"] for a in steps} >= {0, 1}
    assert max(a["sampled"] for a in steps) <= 2
    assert steps[-1]["sampled"] == 0          # the greedy one ends alone
    assert sum(s.attrs["sampled"] for s in spans
               if s.name == "serve/prefill") == 2
    assert all(0 <= t < 61 for h in handles for t in h.tokens)


def test_attn_blocks_follow_admissions_and_retirements(monkeypatch):
    """With the length-bounded kernel (interpreted; the table's word is
    overridden, which no CPU table gives) a step reads the blocks of its
    live slots only, ``ceil((length + 1) / 128)`` each: one request alone
    crosses a block's edge, then three leave one after another."""
    import numpy as np
    from bigdl_tpu.serving import slots as slots_mod
    monkeypatch.setattr(slots_mod, "in_place_applies", lambda *a: True)
    monkeypatch.setattr(slots_mod.decode_attention, "applies",
                        lambda *a: True)
    model = GPTForCausalLM(vocab_size=61, hidden_size=32, n_layers=2,
                           n_heads=4, max_position=256)
    params, _ = model.setup(jax.random.PRNGKey(3), None)
    tracer = obs.default_tracer()
    tracer.clear()
    with ServingEngine(model, params, max_slots=3) as engine:
        loop = engine.scheduler._thread.ident
        assert engine.slots.attn_read == "kernel"
        long = np.arange(126, dtype=np.int32) % 61
        engine.submit(long, 5).result(timeout=300)
        alone = _dispatched(tracer.spans(), loop)
        tracer.clear()
        handles = [engine.submit(PROMPTS[i], n)
                   for i, n in enumerate((2, 4, 6))]
        for h in handles:
            h.result(timeout=300)
    three = _dispatched(tracer.spans(), loop)
    for a in alone + three:
        assert a["attn_read"] == "kernel"
        assert a["attn_blocks_table"] == 3 * 2
    # lengths 126, 127 read one block, 128 and on two
    assert [a["live"] for a in alone] == [1] * len(alone)
    blocks = [a["attn_blocks"] for a in alone]
    assert blocks == sorted(blocks) and blocks[:2] == [1, 1]
    assert blocks[-1] == 2
    # short streams read a block each: the count is the live slots'
    assert all(a["attn_blocks"] == a["live"] for a in three)
    assert {a["attn_blocks"] for a in three} >= {1, 2, 3}
    assert three[-1]["attn_blocks"] == 1


def test_step_seconds_are_the_step_spans():
    """One interval, one clock read: the scheduler's ``step_seconds`` is
    the sum of its ``serve/step`` spans, not a second timing of them."""
    tracer = obs.default_tracer()
    tracer.clear()       # a thread's ident can be that of one that ended
    with _engine() as engine:
        loop = engine.scheduler._thread.ident
        engine.submit(PROMPTS[0], 6).result(timeout=120)
        total = engine.scheduler.step_seconds
    steps = [s for s in tracer.spans()
             if s.thread_id == loop and s.name == "serve/step"]
    assert len(steps) >= 6
    assert total == pytest.approx(sum(s.duration for s in steps))


def test_the_leaves_reach_the_profilers_host_plane(tmp_path):
    """Under a real ``jax.profiler`` session the loop thread's line of
    ``/host:CPU`` holds the leaves, on the device trace's clock, and no
    parent: a parent would take the label of every idle gap under it."""
    with _engine() as engine:
        engine.submit(PROMPTS[0], 2).result(timeout=120)       # compiled
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            engine.submit(PROMPTS[1], 4).result(timeout=120)
        finally:
            jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    (host,) = [p for p in data.planes if p.name == "/host:CPU"]
    names = collections.Counter(e.name for line in host.lines
                                for e in line.events
                                if e.name.startswith("serve/"))
    assert names["serve/step.dispatch"] >= 4
    assert names["serve/step.readback"] >= 4
    assert set(names) <= LEAVES, names
    assert not set(names) & PARENTS
    # the attributes a leaf is opened with are the event's stats
    (line,) = [l for l in host.lines
               if any(e.name == "serve/step.dispatch" for e in l.events)]
    event = next(e for e in line.events if e.name == "serve/step.dispatch")
    assert dict(event.stats)["iter"] >= 1


# the token gap -------------------------------------------------------------
def _delivers(spans):
    return [s for s in spans if s.name == "serve/deliver"]


def _gap_histogram(engine):
    return engine.scheduler._obs["token_gap"]


def test_a_delivery_says_how_long_its_streams_waited(served):
    """Every ``serve/deliver`` carries its block's ``prefills`` and
    ``prefill_positions``; every one that serves a stream the delivery
    before also served carries ``gap_ms``, the distance between the two
    deliveries' starts, and ``gap_streams``; the one without (the first of
    a busy stretch) hands out first tokens only."""
    _, spans = served
    delivers = _delivers(spans)
    assert len(delivers) >= 8
    assert "gap_ms" not in delivers[0].attrs
    waited = 0
    for before, d in zip([None] + delivers, delivers):
        a = d.attrs
        assert a["prefills"] >= 0
        assert a["prefill_positions"] >= 16 * a["prefills"]
        assert ("gap_ms" in a) == ("gap_streams" in a)
        if "gap_ms" in a:
            waited += 1
            assert 1 <= a["gap_streams"] <= a["tokens"]
            assert a["gap_ms"] == pytest.approx(
                1e3 * (d.start - before.start), abs=1e-6)
        else:
            firsts = [s for s in spans if s.name == "serve/first_token"
                      and d.start <= s.end <= d.end]
            assert len(firsts) == a["tokens"]
    assert waited >= len(delivers) - 2
    # the prefills all stood before some block that was delivered
    assert sum(d.attrs["prefills"] for d in delivers) == len(
        [s for s in spans if s.name == "serve/prefill"])
    assert sum(d.attrs["prefill_positions"] for d in delivers) == sum(
        s.attrs["rows"] * s.attrs["bucket"] for s in spans
        if s.name == "serve/prefill")


@pytest.mark.parametrize("paged", [False, True])
def test_a_prefill_lengthens_the_gap_of_the_block_dispatched_after_it(paged):
    """A request admitted while another decodes. The dense loop has block
    N in flight when it launches the prefill, dispatches block N+1 behind
    it and delivers block N in that same iteration: ``prefills`` 0 there,
    and 1 on the next iteration's delivery, which ends the gap the
    prefill lengthened (counted by the host's order, between two
    deliveries, it would be the other way round). The paged loop reads
    each block back at once: the prefill stands before its own
    iteration's block."""
    model = GPTForCausalLM(vocab_size=61, hidden_size=32, n_layers=2,
                           n_heads=4, max_position=64)
    params, _ = model.setup(jax.random.PRNGKey(3), None)
    kw = dict(paged=True, page_size=8, prefill_chunk=8) if paged else {}
    tracer = obs.default_tracer()
    faults.configure(None)
    try:
        with ServingEngine(model, params, max_slots=4, **kw) as engine:
            assert engine.slots.runs_ahead == (not paged)
            loop = engine.scheduler._thread.ident
            engine.submit(PROMPTS[2], 2).result(timeout=300)   # compiled
            tracer.clear()
            faults.configure("serving.step:delay=0.02")
            first = engine.submit(PROMPTS[0], 40)
            deadline = time.monotonic() + 120
            while len(first.tokens) < 3 and time.monotonic() < deadline:
                time.sleep(0.002)
            late = engine.submit(PROMPTS[4], 4)
            late.result(timeout=300)
            faults.configure(None)
            first.result(timeout=300)
    finally:
        faults.configure(None)
    spans = [s for s in tracer.spans() if s.thread_id == loop]
    (pick,) = [s for s in spans if s.name == "serve/pick"
               and s.attrs["n"] == 1 and s.attrs["iter"] > 1
               and s.start > min(d.start for d in _delivers(spans))]
    it = pick.attrs["iter"]
    by_iter = {d.attrs["iter"]: d.attrs for d in _delivers(spans)}
    own, after = by_iter[it], by_iter[it + 1]
    positions = 4 * 8 if paged else 4 * 16      # rows x chunk or bucket
    if paged:
        assert (own["prefills"], own["prefill_positions"]) == (1, positions)
        assert after["prefills"] == 0
        assert own["tokens"] == 2               # the late one's first
    else:
        (step,) = [s for s in spans if s.name == "serve/step"
                   and s.attrs["iter"] == it]
        assert step.attrs["ahead"] == 1
        assert own["prefills"] == own["prefill_positions"] == 0
        assert (after["prefills"], after["prefill_positions"]) == (
            1, positions)
        assert own["tokens"] == 1 and after["tokens"] == 2
    # the first stream waited through both; the late one's first token
    # has no gap
    assert own["gap_streams"] == 1
    assert after["gap_streams"] == (2 if paged else 1)
    assert sum(d["prefills"] for d in by_iter.values()) == 2


def test_a_first_token_and_a_stream_placed_again_have_no_gap():
    """One request alone: every token after its first is one gap. Then a
    step fails under two streams: the recovery places both again from
    their contexts and delivers a block itself, which is no gap of
    theirs (and the block that was in flight is dropped), so each stream
    placed again with tokens delivered counts one gap less."""
    tracer = obs.default_tracer()
    recorder = obs.default_recorder()
    faults.configure(None)
    try:
        with _engine() as engine:
            alone = engine.submit(PROMPTS[0], 7)
            alone.result(timeout=300)
            assert engine.stats["token_gaps"] == 6
            assert _gap_histogram(engine).count == 6
            tracer.clear()
            faults.configure("serving.step:error:after=4:times=1")
            with engine.scheduler._cond:
                pair = [engine.submit(PROMPTS[1], 9),
                        engine.submit(PROMPTS[3], 12)]
            for h in pair:
                h.result(timeout=300)
            assert engine.scheduler.recoveries == 1
            placed_again = sum(
                1 for h in pair
                for e in recorder.timeline(h.trace)["events"]
                if e["event"] == "admit" and e["delivered"] > 0)
            assert placed_again == 2
            gaps = sum(len(h.tokens) - 1 for h in pair) - placed_again
            assert engine.stats["token_gaps"] == 6 + gaps
            assert _gap_histogram(engine).count == 6 + gaps
            loop = engine.scheduler._thread.ident
    finally:
        faults.configure(None)
    # the recovery's own delivery has no leaf and counts no gap: the
    # spans and the sums agree
    delivers = _delivers(s for s in tracer.spans() if s.thread_id == loop)
    assert sum(d.attrs.get("gap_streams", 0) for d in delivers) == gaps
    assert all(h.delivered_at is not None for h in pair)


def test_the_three_sinks_count_the_same_gaps():
    """The histogram's count, ``engine.stats["token_gaps"]`` and the sum
    of ``gap_streams`` over the spans are all the tokens delivered less
    the first tokens; the histogram's sum is the gaps' weighted sum; the
    gaps after a prefill are among them."""
    tracer = obs.default_tracer()
    tracer.clear()
    with _engine() as engine:
        loop = engine.scheduler._thread.ident
        handles = [engine.submit(p, 2 + i % 6)
                   for i, p in enumerate(PROMPTS)]
        for h in handles:
            h.result(timeout=300)
        stats = dict(engine.stats)
        hist = _gap_histogram(engine)
        count, total = hist.count, hist.sum
        scrape = obs.default_registry().prometheus_text()
    delivers = _delivers(s for s in tracer.spans() if s.thread_id == loop)
    gaps = sum(len(h.tokens) - 1 for h in handles)
    assert gaps == sum(d.attrs.get("gap_streams", 0) for d in delivers)
    assert stats["token_gaps"] == count == gaps
    assert total == pytest.approx(sum(
        1e-3 * d.attrs["gap_ms"] * d.attrs["gap_streams"]
        for d in delivers if "gap_ms" in d.attrs))
    after = sum(d.attrs["gap_streams"] for d in delivers
                if d.attrs["prefills"] and "gap_ms" in d.attrs)
    assert 0 < stats["token_gaps_after_prefill"] == after < gaps
    label = engine.scheduler.obs_label
    assert (f'bigdl_serving_token_gap_seconds_count{{engine="{label}"}} '
            f'{gaps}') in scrape


def test_one_observation_counts_for_n():
    bounds = (0.001, 0.01, 0.1)
    once, thrice = HistogramChild(bounds), HistogramChild(bounds)
    once.observe(0.004, n=3)
    once.observe(0.5)
    for _ in range(3):
        thrice.observe(0.004)
    thrice.observe(0.5, n=1)
    assert once.snapshot() == thrice.snapshot()
    assert once.count == 4 and once.sum == pytest.approx(0.512)
    assert once.quantile(0.5) == thrice.quantile(0.5)


def test_the_kill_switch_stops_the_gap_and_serves_the_same_tokens():
    jobs = list(enumerate(PROMPTS[:6]))
    with _engine() as engine:
        on = [engine.submit(p, 3 + i % 4) for i, p in jobs]
        tokens_on = [list(h.result(timeout=300)) for h in on]
        assert engine.stats["token_gaps"] > 0
    tracer = obs.default_tracer()
    tracer.clear()
    was = obs.set_enabled(False)
    try:
        with _engine() as engine:
            off = [engine.submit(p, 3 + i % 4) for i, p in jobs]
            tokens_off = [list(h.result(timeout=300)) for h in off]
            assert engine.stats["token_gaps"] == 0
            assert engine.stats["token_gaps_after_prefill"] == 0
            assert _gap_histogram(engine).count == 0
            assert engine.scheduler._delivered_at is None
            assert engine.scheduler.generated_tokens == sum(
                3 + i % 4 for i, _ in jobs)
    finally:
        obs.set_enabled(was)
    assert tokens_off == tokens_on
    assert all(h.delivered_at is None for h in off)
    assert not [s for s in tracer.spans() if s.name == "serve/deliver"]
