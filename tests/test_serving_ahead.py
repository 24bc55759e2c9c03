"""The serving loop keeps one decode block in flight (docs/serving.md).

Where the slot manager's next block needs nothing of the last block's
tokens (``SlotManager.runs_ahead``), an iteration dispatches block N+1 and
only then reads back and delivers block N. What is held here, on the CPU
at a tiny size: (a) the same requests give the same tokens as through the
same loop held at depth 0; (b) the order of the leaves, and ``ahead``;
(c) a stream that ends by count costs no junk block, one that ends by
EOS or ``cancel()`` exactly one, and its slot serves the next admission;
(d) a fault with a block in flight is one recovery; (e) a drain delivers
the last block, and what settles or journals with a block in flight sees
only what the callers have; (f) the speculative and the paged manager say
that they cannot run ahead.
"""

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import weights
from bigdl_tpu import obs
from bigdl_tpu.models.gpt import GPTForCausalLM
from bigdl_tpu.models.lfm2 import LFM2ForCausalLM
from bigdl_tpu.resilience import faults
from bigdl_tpu.serving import (Request, RequestCancelledError, Scheduler,
                               ServingEngine, SlotManager)
from bigdl_tpu.serving.paging import PagedSlotManager
from bigdl_tpu.serving.snapshot import (KVSnapshot, RequestJournal,
                                        requests_from_journal)

WAIT = 300
PROMPTS = [[5, 9, 2, 17, 3], [1, 1, 4, 60, 8], [7, 3, 3],
           [9, 9, 9, 1, 0, 2, 4], [2, 4], [11, 12, 13, 14, 15, 16],
           list(range(1, 20))]
LFM2_KW = dict(vocab_size=97, hidden_size=32, intermediate_size=48,
               moe_intermediate_size=24,
               layer_types=["conv", "full_attention", "conv", "conv"],
               num_dense_layers=1, num_experts=8, num_experts_per_tok=2,
               num_attention_heads=4, num_key_value_heads=2, conv_L_cache=3,
               max_position=64)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.configure(None)
    yield
    faults.configure(None)


@pytest.fixture(scope="module")
def gpt2():
    model = GPTForCausalLM(vocab_size=61, hidden_size=32, n_layers=2,
                           n_heads=4, max_position=64)
    params, _ = model.setup(jax.random.PRNGKey(3), None)
    return model, params


@pytest.fixture(scope="module")
def lfm2():
    model = LFM2ForCausalLM(**LFM2_KW)
    shapes = jax.eval_shape(lambda k: model.setup(k, None)[0],
                            jax.random.key(0))
    return model, weights.make_params(
        shapes, 5, {"std": 0.2, "gain_std": 0.1, "bias_std": 0.1})


def _depth_zero(monkeypatch):
    """Hold the loop at depth 0: the dense manager says, as the paged and
    the speculative one do, that it cannot run ahead."""
    monkeypatch.setattr(SlotManager, "runs_ahead", False)


def _serve_all(engine, jobs):
    """Submit every ``(prompt, max_new_tokens, keywords)`` while the loop
    cannot pick (it needs the lock that is held here), so that every run
    admits the same requests in the same iterations; returns the handles,
    finished."""
    with engine.scheduler._cond:
        handles = [engine.submit(p, n, **kw) for p, n, kw in jobs]
    for h in handles:
        h.result(timeout=WAIT)
    return handles


def _oracle(model, params, prompt, n_new):
    return [int(t) for t in np.asarray(model.generate(
        params, jnp.asarray(prompt, jnp.int32)[None], n_new))[0]
        [len(prompt):]]


def _drained(engine):
    """Wait until no block is in flight: a junk slot-block is counted when
    its block is read, an iteration after its stream's handle resolved."""
    deadline = time.monotonic() + 30
    while engine.scheduler._flight and time.monotonic() < deadline:
        time.sleep(0.005)
    assert not engine.scheduler._flight


def _loop_spans(tracer, engine):
    loop = engine.scheduler._thread.ident
    return [s for s in tracer.spans() if s.thread_id == loop]


# (a) the same tokens at depth 1 and at depth 0 ----------------------------
@pytest.mark.parametrize("which, temperature, steps_per_sync", [
    ("gpt2", 0.0, 1), ("gpt2", 0.9, 1), ("gpt2", 0.9, 3),
    ("lfm2", 0.0, 1), ("lfm2", 0.9, 1)])
def test_ahead_and_depth_zero_give_the_same_tokens(
        request, monkeypatch, which, temperature, steps_per_sync):
    """Seven requests over three slots, so that slots are taken again:
    greedy ones, or four of seven sampled under a fixed engine seed, every
    stream ended by its count. The key is split once in each block in
    which some slot has a temperature, so the draws agree block for
    block."""
    model, params = request.getfixturevalue(which)
    jobs = [(p, 4 + 3 * (i % 4),
             {"temperature": temperature if i % 2 == 0 else 0.0})
            for i, p in enumerate(PROMPTS)]
    kw = dict(max_slots=3, steps_per_sync=steps_per_sync, seed=11,
              top_k=20, top_p=0.95)

    def run():
        with ServingEngine(model, params, **kw) as engine:
            handles = _serve_all(engine, jobs)
            return [list(h.tokens) for h in handles], engine.metrics()

    ahead, met = run()
    assert met["steps_ahead"] > 0 and met["junk_slot_blocks"] == 0
    _depth_zero(monkeypatch)
    plain, met = run()
    assert met["steps_ahead"] == 0 and met["junk_slot_blocks"] == 0
    assert ahead == plain
    assert [len(t) for t in ahead] == [n for _, n, _ in jobs]
    if which == "gpt2":
        # greedy streams are the model's own; the sampled ones are drawn
        oracle = [_oracle(model, params, p, n) for p, n, _ in jobs]
        drawn = [got != want for got, want in zip(ahead, oracle)]
        assert not any(drawn[1::2])
        assert any(drawn[0::2]) == (temperature > 0.0)


# (b) the order of the leaves ----------------------------------------------
def test_a_block_is_dispatched_before_the_one_before_it_is_read(gpt2):
    """One busy stretch: every step but the first is dispatched with a
    block in flight (``ahead`` 1), its dispatch leaf opens before the
    readback leaf of the same iteration closes, and what that readback
    brings is the block of the iteration BEFORE: as many tokens as that
    one had live streams."""
    model, params = gpt2
    tracer = obs.default_tracer()
    tracer.clear()
    with ServingEngine(model, params, max_slots=4) as engine:
        _serve_all(engine, [(p, 5 + i, {}) for i, p in enumerate(PROMPTS)])
        spans = _loop_spans(tracer, engine)
        met = engine.metrics()
    by_iter = collections.defaultdict(dict)
    for s in spans:
        if "iter" in s.attrs:
            by_iter[s.attrs["iter"]][s.name] = s
    steps = [by_iter[i] for i in sorted(by_iter) if "serve/step" in by_iter[i]]
    dispatching = [it for it in steps if "live" in it["serve/step"].attrs]
    assert [it["serve/step"].attrs["ahead"] for it in dispatching] == \
        [0] + [1] * (len(dispatching) - 1)
    assert met["steps_ahead"] == len(dispatching) - 1 > 10
    # the last iteration of the stretch only drains
    assert steps[-1] is not dispatching[-1]
    assert "serve/step.dispatch" not in steps[-1]
    assert steps[-1]["serve/step"].attrs["ahead"] == 0
    for before, it in zip(steps, steps[1:]):
        read, step = it["serve/step.readback"], it["serve/step"]
        assert step.start <= read.start and read.end <= step.end
        if "serve/step.dispatch" in it:
            assert it["serve/step.dispatch"].end <= read.start
        assert read.end <= it["serve/deliver"].start
        # steps_per_sync 1, no EOS: a token a live stream of that block
        assert it["serve/deliver"].attrs["tokens"] == \
            before["serve/step"].attrs["live"]


# (c) what a stream's end costs --------------------------------------------
def test_an_end_by_count_or_by_the_tables_room_costs_no_junk_block(gpt2):
    """``max_new_tokens`` and the table's room are arithmetic the host has
    at the dispatch: such a slot is out of the block after its last
    token. The second scheduler takes a request that outgrows the table
    (past the engine's check, as tests/test_serving.py does)."""
    model, params = gpt2
    with ServingEngine(model, params, max_slots=2) as engine:
        handles = _serve_all(engine, [(p, 3 + i, {})
                                      for i, p in enumerate(PROMPTS)])
        met = engine.metrics()
    assert met["junk_slot_blocks"] == 0 and met["steps_ahead"] > 0
    # every block was computed for live streams only: a dispatch a prefill,
    # a dispatch a block, and a block for every token of the longest tail
    assert met["generated_tokens"] == sum(len(h.tokens) for h in handles)
    sm = SlotManager(model, params, max_slots=2, steps_per_sync=4)
    sch = Scheduler(sm, max_queue=4)
    try:
        r = Request(PROMPTS[0], max_new_tokens=200)       # 5 + 200 > 64
        sch.submit(r)
        out = r.result(timeout=WAIT)
    finally:
        sch.shutdown(drain=False, timeout=60)
    assert r.truncated and out.size == 64
    assert out[5:].tolist() == _oracle(model, params, PROMPTS[0], 59)
    assert sm.stats["junk_slot_blocks"] == 0 and sm.stats["steps_ahead"] > 0
    # 59 tokens in blocks of 4: fifteen blocks and one prefill
    assert sm.stats["dispatches"] == 1 + 15


def test_an_eos_costs_one_junk_block_and_the_slot_serves_the_next(gpt2):
    """The EOS is seen when its block is read, with the next one already
    on the device: one block of junk in the stream's own row, nothing of
    it delivered; the slot is free at once and the next admission
    overwrites the row."""
    model, params = gpt2
    want = _oracle(model, params, PROMPTS[0], 20)
    # a token whose FIRST occurrence lies inside the stream
    k = next(i for i in range(3, 15) if want[i] not in want[:i])
    with ServingEngine(model, params, max_slots=1) as engine:
        h = engine.submit(PROMPTS[0], 20, eos_token=want[k])
        assert h.result(timeout=WAIT)[len(PROMPTS[0]):].tolist() \
            == want[:k + 1]
        assert list(h) == want[:k + 1]
        _drained(engine)
        assert engine.metrics()["junk_slot_blocks"] == 1
        dispatched = engine.metrics()["dispatches"]
        assert dispatched == 1 + (k + 1) + 1      # prefill, blocks, the junk
        again = engine.submit(PROMPTS[1], 9)
        assert again.result(timeout=WAIT)[len(PROMPTS[1]):].tolist() \
            == _oracle(model, params, PROMPTS[1], 9)
        met = engine.metrics()
    assert met["junk_slot_blocks"] == 1
    assert met["generated_tokens"] == k + 1 + 9
    assert met["retired"] == 2


def test_a_cancel_costs_one_junk_block_and_the_slot_serves_the_next(gpt2):
    model, params = gpt2
    faults.configure("serving.step:delay=0.02")
    with ServingEngine(model, params, max_slots=1) as engine:
        running = engine.submit(PROMPTS[0], 50)
        it = iter(running)
        got = [next(it) for _ in range(3)]
        assert running.cancel()
        with pytest.raises(RequestCancelledError):
            running.result(timeout=WAIT)
        faults.configure(None)
        _drained(engine)
        assert engine.metrics()["junk_slot_blocks"] == 1
        assert got == _oracle(model, params, PROMPTS[0], 3)
        again = engine.submit(PROMPTS[2], 7)
        assert again.result(timeout=WAIT)[len(PROMPTS[2]):].tolist() \
            == _oracle(model, params, PROMPTS[2], 7)
        met = engine.metrics()
    assert met["junk_slot_blocks"] == 1 and met["cancelled"] == 1
    # nothing of the block in flight at the cancel reached the caller
    assert running.tokens == _oracle(model, params, PROMPTS[0],
                                     len(running.tokens))
    assert met["generated_tokens"] == len(running.tokens) + 7


# (d) a fault with a block in flight ---------------------------------------
@pytest.mark.parametrize("where", ["dispatch", "readback"])
def test_a_fault_with_a_block_in_flight_is_one_recovery(
        gpt2, monkeypatch, where):
    """At the fault point before a dispatch, or out of the readback of
    block N with N+1 on the device: the block in flight goes with the
    table, every stream is re-placed once from what it was delivered, and
    the tokens are those of an undisturbed run."""
    model, params = gpt2
    jobs = [(p, 8 + i, {}) for i, p in enumerate(PROMPTS[:5])]
    if where == "dispatch":
        faults.configure("serving.step:error:after=3:times=1")
    else:
        real, calls = SlotManager.read_step, []

        def failing(self, block):
            calls.append(block)
            if len(calls) == 3:
                raise RuntimeError("lost the block")
            return real(self, block)

        monkeypatch.setattr(SlotManager, "read_step", failing)
    with ServingEngine(model, params, max_slots=3) as engine:
        handles = _serve_all(engine, jobs)
        met = engine.metrics()
    assert met["failures"] == 1 and met["recoveries"] == 1
    assert met["quarantined"] == 0 and met["retired"] == len(jobs)
    for (p, n, _), h in zip(jobs, handles):
        assert h.tokens == _oracle(model, params, p, n)
    assert met["generated_tokens"] == sum(n for _, n, _ in jobs)


# (e) what drains ----------------------------------------------------------
def test_a_draining_shutdown_delivers_the_block_in_flight(gpt2):
    model, params = gpt2
    engine = ServingEngine(model, params, max_slots=2)
    handles = [engine.submit(p, 6 + i) for i, p in enumerate(PROMPTS[:5])]
    assert engine.shutdown(drain=True, timeout=WAIT)
    for i, (p, h) in enumerate(zip(PROMPTS, handles)):
        assert h.done.is_set() and h.error is None
        assert h.tokens == _oracle(model, params, p, 6 + i)
    assert not engine.scheduler._flight and not engine.scheduler._inflight


def test_what_settles_sees_the_table_as_of_the_tokens_delivered(
        gpt2, monkeypatch):
    """``_settle`` is what a page snapshot and a preemption call before
    they look: it reads back and delivers the block in flight, after
    which every slot's length is its stream's context, and the loop
    starts a stretch again with the same tokens."""
    model, params = gpt2
    seen = []

    def settle_then_look(self, force=False):
        before = len(self._flight)
        self._settle()
        seen.append((before, len(self._flight), all(
            int(self.slots.lengths[s]) == r.context().size
            for s, r in self._inflight.items())))

    monkeypatch.setattr(Scheduler, "_maybe_snapshot", settle_then_look)
    jobs = [(p, 5 + i, {}) for i, p in enumerate(PROMPTS[:4])]
    with ServingEngine(model, params, max_slots=2) as engine:
        handles = _serve_all(engine, jobs)
        met = engine.metrics()
    assert any(before == 1 for before, _, _ in seen)
    assert all(after == 0 and agree for _, after, agree in seen)
    assert met["steps_ahead"] == 0 and met["junk_slot_blocks"] == 0
    for (p, n, _), h in zip(jobs, handles):
        assert h.tokens == _oracle(model, params, p, n)


def test_a_journal_cut_with_a_block_in_flight_resumes_the_same_stream(
        gpt2, tmp_path):
    """The journal holds what was DELIVERED: a loop abandoned with a
    block in flight leaves no token of that block in it, and the streams
    rebuilt from it continue to the undisturbed continuation."""
    model, params = gpt2
    faults.configure("serving.step:delay=0.02")
    snap = KVSnapshot(str(tmp_path))
    sch = Scheduler(SlotManager(model, params, max_slots=3), snapshot=snap)
    reqs = [Request(p, 30) for p in PROMPTS[:3]]
    for r in reqs:
        sch.submit(r)
    while min(len(r.tokens) for r in reqs) < 4:
        time.sleep(0.01)
    victims = sch.abandon()
    sch._thread.join(WAIT)
    faults.configure(None)
    assert len(victims) == 3
    snap.flush()
    snap.close()
    entries = RequestJournal.replay(str(tmp_path / "journal.jsonl"))
    assert len(entries) == 3
    rebuilt = requests_from_journal(entries)
    with ServingEngine(model, params, max_slots=3) as engine:
        for r in rebuilt:
            engine.resubmit(r)
        for r in rebuilt:
            r.result(timeout=WAIT)
    for r in rebuilt:
        journalled = entries[[k for k, e in entries.items()
                              if e["prompt"] == r.prompt.tolist()][0]]
        assert 4 <= len(journalled["tokens"]) < 30
        assert r.tokens == _oracle(model, params, r.prompt.tolist(), 30)


def test_callers_that_cancel_from_many_threads_leave_the_table_whole(gpt2):
    """More caller threads than cores against the loop thread, the
    interpreter switching every 10 us: each submits, reads a few tokens
    and cancels or reads on. Every handle resolves, what a caller got is
    a prefix of the model's own continuation, and at the end no slot, no
    block and no stream is held; a junk slot-block needs a cancel."""
    import sys
    import threading
    model, params = gpt2
    oracle = {i: _oracle(model, params, p, 24) for i, p in enumerate(PROMPTS)}
    results, lock = [], threading.Lock()

    def caller(k, engine):
        rng = np.random.default_rng(k)
        for _ in range(3):
            i = int(rng.integers(len(PROMPTS)))
            h = engine.submit(PROMPTS[i], int(rng.integers(2, 25)))
            stop = int(rng.integers(0, 6))
            try:
                for n, _ in enumerate(h, 1):
                    if stop and n == stop:
                        h.cancel()
                err = None
            except RequestCancelledError as e:
                err = e
            with lock:
                results.append((i, h, err))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with ServingEngine(model, params, max_slots=3,
                           max_queue=64) as engine:
            threads = [threading.Thread(target=caller, args=(k, engine))
                       for k in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(WAIT)
            assert not any(t.is_alive() for t in threads)
            _drained(engine)  # the last cancel's one junk block
            met = engine.metrics()
            assert not engine.scheduler._inflight
            assert engine.slots.occupancy() == 0
    finally:
        sys.setswitchinterval(interval)
    assert len(results) == 48
    for i, h, err in results:
        assert h.done.is_set()
        assert h.tokens == oracle[i][:len(h.tokens)]
        if err is None:
            assert len(h.tokens) == h.max_new_tokens
    assert met["retired"] + met["cancelled"] == 48
    assert met["junk_slot_blocks"] <= met["cancelled"]


# (f) who stays in today's order -------------------------------------------
def test_the_speculative_and_the_paged_manager_cannot_run_ahead(gpt2):
    model, params = gpt2
    assert SlotManager(model, params, max_slots=2).runs_ahead
    assert not SlotManager(model, params, max_slots=2,
                           spec_tokens=3).runs_ahead
    assert not PagedSlotManager(model, params, max_slots=2, num_pages=16,
                                page_size=8).runs_ahead
    tracer = obs.default_tracer()
    for kw in ({"spec_tokens": 3}, {"paged": True, "page_size": 8}):
        tracer.clear()
        with ServingEngine(model, params, max_slots=2, **kw) as engine:
            handles = _serve_all(engine, [(p, 6, {}) for p in PROMPTS[:4]])
            met = engine.metrics()
            steps = [s for s in _loop_spans(tracer, engine)
                     if s.name == "serve/step"]
        assert met["steps_ahead"] == 0 and met["junk_slot_blocks"] == 0
        assert steps and {s.attrs["ahead"] for s in steps} == {0}
        assert all("live" in s.attrs for s in steps)     # none only drains
        for p, h in zip(PROMPTS, handles):
            assert h.tokens == _oracle(model, params, p, 6)
