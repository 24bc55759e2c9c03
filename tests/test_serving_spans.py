"""The serving loop named phase by phase (docs/observability.md).

A tiny engine on the CPU serves a dozen requests; what is held: every
leaf of one loop iteration carries that iteration's ``iter``, every
request gets exactly one ``serve/queue_wait`` and one ``serve/first_token``
that meet at its admission, a ``serve/prefill`` counts no more prompt
tokens than positions it computed, an iteration opens at most 12 spans,
and under a real profiler session the leaves (and no parent) lie on the
``/host:CPU`` plane.
"""

import collections
import glob
import os

import jax
import pytest

from bigdl_tpu import obs
from bigdl_tpu.models.gpt import GPTForCausalLM
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
