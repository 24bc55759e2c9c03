"""Continuous-batching serving engine (bigdl_tpu/serving/).

The contract under test (ISSUE 4 acceptance): (a) N concurrent requests
through the engine produce token-identical output (temperature 0) to N
sequential ``generate`` calls, including requests admitted mid-flight;
(b) the engine step function compiles at most twice and dispatches O(1)
per generated token across the whole workload; (c) a full queue rejects
with a clean error and ``shutdown()`` drains in-flight requests.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.models.gpt import GPTForCausalLM
from bigdl_tpu.parallel.sequence import (MultiHeadAttention,
                                         cached_attention, full_attention)
from bigdl_tpu.serving import (EngineClosedError, QueueFullError,
                               ServingEngine, SlotManager)


def _tiny(**kw):
    cfg = dict(vocab_size=61, hidden_size=32, n_layers=2, n_heads=4,
               max_position=64)
    cfg.update(kw)
    return GPTForCausalLM(**cfg)


def _built(seed=0, **kw):
    m = _tiny(**kw)
    params, _ = m.setup(jax.random.PRNGKey(seed), None)
    return m, params


PROMPTS = [[5, 9, 2, 17, 3], [1, 1, 4, 60, 8], [7, 3, 3],
           [9, 9, 9, 1, 0, 2, 4], [2, 4], [11, 12, 13, 14, 15, 16]]


def _sequential(m, params, prompts, n_new):
    """The oracle: N batch-1 ``generate`` calls, one after another."""
    return [np.asarray(m.generate(params, jnp.asarray(p, jnp.int32)[None],
                                  n_new))[0]
            for p in prompts]


# ---------------------------------------------------- per-slot primitives --
def test_cached_attention_per_row_lengths():
    """Vector cur_len: each row must equal full attention restricted to
    its own filled prefix."""
    rng = np.random.default_rng(0)
    b, h, s, d = 3, 4, 16, 8
    q = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    curs = jnp.asarray([3, 9, 16], jnp.int32)
    out = cached_attention(q, k, v, curs)
    for i, c in enumerate([3, 9, 16]):
        ref = full_attention(q[i:i + 1], k[i:i + 1, :, :c],
                             v[i:i + 1, :, :c])
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(ref[0]),
                                   atol=1e-5)


def test_mha_decode_step_vector_index_matches_scalar():
    """A vector index of identical positions must reproduce the scalar
    path bitwise (same writes, same masks)."""
    mha = MultiHeadAttention(32, 4, causal=True)
    params, _ = mha.setup(jax.random.PRNGKey(1), None)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((3, 6, 32)), jnp.float32)
    cache = mha.init_cache(3, 16)
    _, cache = mha.prefill(params, x[:, :5], cache)
    out_s, cache_s = mha.decode_step(params, x[:, 5:6], cache, 5)
    out_v, cache_v = mha.decode_step(params, x[:, 5:6], cache,
                                     jnp.asarray([5, 5, 5], jnp.int32))
    np.testing.assert_array_equal(np.asarray(out_s), np.asarray(out_v))
    np.testing.assert_array_equal(np.asarray(cache_s["k"]),
                                  np.asarray(cache_v["k"]))


def test_slot_manager_bookkeeping():
    m, params = _built()
    sm = SlotManager(m, params, max_slots=3, window=2)
    assert sm.free_slots() == 3 and sm.occupancy() == 0
    slots = sm.admit([np.asarray([5, 9, 2]), np.asarray([1, 2, 3, 4])])
    assert slots == [0, 1]
    assert sm.occupancy() == 2
    np.testing.assert_array_equal(sm.lengths[:2], [3, 4])
    toks = sm.step()
    assert toks.shape == (1, 3)
    np.testing.assert_array_equal(sm.lengths[:2], [4, 5])
    sm.retire(0)
    assert sm.free_slots() == 2 and not sm.active[0]
    with pytest.raises(ValueError, match="not active"):
        sm.retire(0)
    # the freed lowest slot is reused first (deterministic placement)
    assert sm.admit([np.asarray([8, 8])]) == [0]
    with pytest.raises(ValueError, match="exceeds window"):
        sm.admit([np.asarray([1])] * 3)


# ------------------------------------------------------- (a) token parity --
def test_concurrent_engine_matches_sequential_generate():
    """Acceptance (a): N concurrent requests == N sequential generate
    calls, token-identical at temperature 0 — with fewer slots than
    requests, so admission interleaves with decoding."""
    m, params = _built()
    n_new = 12
    expected = _sequential(m, params, PROMPTS, n_new)
    engine = ServingEngine(m, params, max_slots=3, max_queue=16,
                           prefill_window=2)
    handles = [engine.submit(p, n_new) for p in PROMPTS]
    results = [engine.result(h, timeout=120) for h in handles]
    engine.shutdown()
    for exp, got in zip(expected, results):
        np.testing.assert_array_equal(exp, got)


def test_mid_flight_admission_parity():
    """Acceptance (a), arrival-order half: requests submitted while
    earlier ones are mid-generation join the running batch and still
    produce the sequential tokens."""
    m, params = _built(seed=2)
    n_new = 16
    expected = _sequential(m, params, PROMPTS, n_new)
    engine = ServingEngine(m, params, max_slots=4, max_queue=16)
    first = [engine.submit(p, n_new) for p in PROMPTS[:2]]
    # wait until the first wave is demonstrably mid-flight (first token
    # out, generation not finished), then admit the rest
    stream = engine.stream(first[0])
    next(stream)
    assert not first[0].done.is_set()
    late = [engine.submit(p, n_new) for p in PROMPTS[2:]]
    results = ([engine.result(h, timeout=120) for h in first]
               + [engine.result(h, timeout=120) for h in late])
    engine.shutdown()
    for exp, got in zip(expected, results):
        np.testing.assert_array_equal(exp, got)


def test_steps_per_sync_block_parity():
    """Fusing K decode steps per dispatch must not change tokens: a
    request finishing mid-block has its tail junk discarded."""
    m, params = _built(seed=3)
    n_new = 10   # not a multiple of the block size
    expected = _sequential(m, params, PROMPTS[:4], n_new)
    engine = ServingEngine(m, params, max_slots=4, steps_per_sync=4)
    handles = [engine.submit(p, n_new) for p in PROMPTS[:4]]
    results = [engine.result(h, timeout=120) for h in handles]
    engine.shutdown()
    for exp, got in zip(expected, results):
        np.testing.assert_array_equal(exp, got)
    assert all(len(h.tokens) == n_new for h in handles)


def test_eos_token_retires_early():
    """EOS stops a request at the matching token; the tail of the slot's
    block is discarded and the slot is reused."""
    m, params = _built()
    n_new = 12
    [expected] = _sequential(m, params, PROMPTS[:1], n_new)
    prompt_len = len(PROMPTS[0])
    gen = expected[prompt_len:]
    eos = int(gen[3])                 # stops at its FIRST occurrence
    cut = int(np.argmax(gen == eos)) + 1
    assert cut < n_new                # the test must exercise early stop
    engine = ServingEngine(m, params, max_slots=2)
    h = engine.submit(PROMPTS[0], n_new, eos_token=eos)
    got = engine.result(h, timeout=60)
    engine.shutdown()
    np.testing.assert_array_equal(expected[:prompt_len + cut], got)
    assert got[-1] == eos


def test_streaming_yields_the_result_tokens():
    m, params = _built(seed=4)
    n_new = 8
    engine = ServingEngine(m, params, max_slots=2)
    h = engine.submit(PROMPTS[1], n_new)
    streamed = list(engine.stream(h))
    res = engine.result(h)
    engine.shutdown()
    assert streamed == h.tokens and len(streamed) == n_new
    np.testing.assert_array_equal(
        res, np.concatenate([np.asarray(PROMPTS[1]), streamed]))


def test_sampled_requests_complete_and_diverge_from_greedy():
    """temperature > 0 rides the same step executable (per-slot
    ``jnp.where``); near-uniform sampling must diverge from greedy."""
    m, params = _built(seed=5)
    n_new = 16
    engine = ServingEngine(m, params, max_slots=2, top_k=16)
    greedy = engine.submit(PROMPTS[0], n_new)
    hot = engine.submit(PROMPTS[0], n_new, temperature=8.0)
    g, s = engine.result(greedy, timeout=60), engine.result(hot, timeout=60)
    st = engine.stats
    engine.shutdown()
    assert st["step_traces"] == 1     # both modes share one executable
    assert len(g) == len(s) == len(PROMPTS[0]) + n_new
    assert int(s.max()) < m.vocab_size and int(s.min()) >= 0
    assert not np.array_equal(g, s)


# --------------------------------------- (b) compile & dispatch frugality --
def test_step_compiles_once_and_dispatches_o1_per_token():
    """Acceptance (b): across a whole multi-wave workload with varied
    arrival order the step function compiles once (≤2 allowed) and total
    dispatches stay O(1) per generated token."""
    m, params = _built(seed=6)
    n_new = 8
    engine = ServingEngine(m, params, max_slots=3, prefill_window=2)
    # wave 1: saturating burst; wave 2: trickle arrivals
    for h in [engine.submit(p, n_new) for p in PROMPTS]:
        engine.result(h, timeout=120)
    for p in PROMPTS[:3]:
        engine.result(engine.submit(p, n_new), timeout=120)
        time.sleep(0.01)
    st = dict(engine.stats)
    generated = engine.scheduler.generated_tokens
    engine.shutdown()
    assert st["step_traces"] <= 2       # expected: exactly 1
    assert st["prefill_traces"] <= 2    # one shared prompt bucket
    # every dispatch is either one admission batch or one token step that
    # yields >= 1 useful token — O(1) per token overall
    n_requests = len(PROMPTS) + 3
    assert st["dispatches"] <= n_requests + generated
    assert generated == n_requests * n_new


def test_single_request_dispatch_count_exact():
    """One lonely request: exactly 1 admission dispatch + n_new step
    dispatches (steps_per_sync=1) — no hidden extra launches."""
    m, params = _built(seed=7)
    n_new = 6
    engine = ServingEngine(m, params, max_slots=2)
    engine.result(engine.submit(PROMPTS[2], n_new), timeout=60)
    st = dict(engine.stats)
    engine.shutdown()
    assert st["dispatches"] == 1 + n_new
    assert st["prefill_traces"] == 1 and st["step_traces"] == 1


# ------------------------------------- (c) backpressure, shutdown, errors --
def test_full_queue_rejects_cleanly():
    """Acceptance (c1): waiting queue at max_queue -> QueueFullError;
    already-queued work is unaffected and completes."""
    m, params = _built(max_position=256)
    expected = _sequential(m, params, [PROMPTS[0]] * 3, 8)
    engine = ServingEngine(m, params, max_slots=1, max_queue=2)
    # slot pinned by a long-running request, queue filled to the brim
    long = engine.submit([1, 2, 3, 4], 200)
    next(engine.stream(long))      # first token out => slot is occupied
    queued = [engine.submit(PROMPTS[0], 8) for _ in range(2)]
    with pytest.raises(QueueFullError, match="retry later"):
        engine.submit(PROMPTS[0], 8)
    assert engine.metrics()["rejected"] == 1
    results = [engine.result(h, timeout=300) for h in queued]
    engine.result(long, timeout=300)
    engine.shutdown()
    for exp, got in zip(expected, results):
        np.testing.assert_array_equal(exp, got)


def test_overlong_request_rejected_upfront():
    m, params = _built()   # max_position 64
    engine = ServingEngine(m, params, max_slots=1)
    with pytest.raises(ValueError, match="max_position"):
        engine.submit(list(range(10)), 60)
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.submit(PROMPTS[0], 0)
    with pytest.raises(ValueError, match="empty prompt"):
        engine.submit([], 4)
    engine.shutdown()


def test_admit_rejects_overlong_prompt_and_leaks_no_slot():
    """A prompt the slot table cannot hold alongside one generated
    token is rejected at admission with a clear error."""
    m, params = _built()          # max_position 64
    sm = SlotManager(m, params, max_slots=2)
    with pytest.raises(ValueError, match="slot capacity of 63"):
        sm.admit([list(range(64))])
    assert sm.free_slots() == 2


def test_request_truncated_at_max_position():
    """A request whose ``prompt_len + generated`` reaches
    ``max_position`` is force-retired with ``Request.truncated`` set —
    a short successful result, never clamped-position junk
    (scheduler-level, bypassing the submit bound check)."""
    from bigdl_tpu.serving import Request, Scheduler
    m, params = _built(seed=12)
    sm = SlotManager(m, params, max_slots=2, steps_per_sync=4)
    sch = Scheduler(sm, max_queue=4)
    try:
        r = Request(PROMPTS[0], max_new_tokens=200)   # 5 + 200 > 64
        sch.submit(r)
        out = r.result(timeout=120)
    finally:
        sch.shutdown(drain=False, timeout=60)
    assert r.truncated and r.error is None
    assert out.size == m.gpt.max_position             # filled to the brim
    # the delivered prefix is still the true greedy continuation
    [oracle] = _sequential(m, params, [PROMPTS[0]], 59)
    np.testing.assert_array_equal(oracle, out)


def test_exact_fit_request_completes_untruncated():
    """prompt + max_new_tokens == max_position is legal and NOT marked
    truncated: the cap and the natural end coincide."""
    m, params = _built(seed=13)
    engine = ServingEngine(m, params, max_slots=2)
    h = engine.submit(PROMPTS[4], 62)                 # 2 + 62 == 64
    out = engine.result(h, timeout=120)
    engine.shutdown()
    assert out.size == 64 and len(h.tokens) == 62
    assert not h.truncated


def test_shutdown_drains_in_flight_and_queued():
    """Acceptance (c2): graceful shutdown serves everything already
    accepted, then rejects new submissions."""
    m, params = _built(seed=8)
    n_new = 12
    expected = _sequential(m, params, PROMPTS, n_new)
    engine = ServingEngine(m, params, max_slots=2, max_queue=16)
    handles = [engine.submit(p, n_new) for p in PROMPTS]
    engine.shutdown(drain=True, timeout=300)    # blocks until drained
    for exp, h in zip(expected, handles):
        assert h.done.is_set()
        np.testing.assert_array_equal(exp, h.result(timeout=0.1))
    with pytest.raises(EngineClosedError):
        engine.submit(PROMPTS[0], 4)


def test_shutdown_without_drain_cancels():
    m, params = _built(max_position=256)
    engine = ServingEngine(m, params, max_slots=1, max_queue=8)
    inflight = engine.submit([1, 2, 3, 4], 200)
    queued = engine.submit(PROMPTS[0], 8)
    engine.shutdown(drain=False, timeout=60)
    for h in (inflight, queued):
        with pytest.raises(EngineClosedError):
            h.result(timeout=10)


def test_metrics_shape_and_counters():
    m, params = _built(seed=9)
    with ServingEngine(m, params, max_slots=2) as engine:
        for h in [engine.submit(p, 6) for p in PROMPTS[:3]]:
            engine.result(h, timeout=60)
        met = engine.metrics()
    assert met["admitted"] == met["retired"] == 3
    assert met["rejected"] == 0
    assert met["queue_depth"] == 0 and met["slot_occupancy"] == 0
    assert met["generated_tokens"] == 18
    assert met["time_to_first_token_s"] > 0
    assert met["decode_tokens_per_sec"] > 0
    assert met["step_traces"] >= 1 and met["dispatches"] > 0


def test_engine_rejects_unbuilt_and_non_kv_models():
    m = _tiny()
    with pytest.raises(ValueError, match="before serving"):
        ServingEngine(m)
    from bigdl_tpu import nn
    mlp = nn.Sequential(nn.Linear(4, 4)).build(0, (2, 4))
    with pytest.raises(TypeError, match="protocol"):
        ServingEngine(mlp)


def test_prediction_service_generate_route():
    """The PredictionService facade gains the engine-backed generate
    route next to one-shot predict."""
    from bigdl_tpu.optim import PredictionService
    m, params = _built(seed=10)
    m.build(0, (1, 8))
    m.params = params       # serve the same weights generate() sees
    expected = _sequential(m, params, PROMPTS[:2], 8)
    svc = PredictionService(m, engine=ServingEngine(m, params,
                                                    max_slots=2))
    got = [svc.generate(p, 8, timeout=60) for p in PROMPTS[:2]]
    svc._engine.shutdown()
    for exp, g in zip(expected, got):
        np.testing.assert_array_equal(exp, g)
    svc_plain = PredictionService(m)
    with pytest.raises(ValueError, match="no serving engine"):
        svc_plain.generate(PROMPTS[0], 4)


# ------------------------------------------------------------------ soak --
@pytest.mark.slow
def test_serving_soak_random_arrivals():
    """Long randomized workload: 40 requests, mixed lengths/temperatures,
    arrivals staggered from worker threads. Every greedy request must
    match its sequential oracle, every sampled request must complete,
    and the compile gates must hold through it all."""
    m, params = _built(seed=11, max_position=128)
    rng = np.random.default_rng(11)
    n_req = 40
    prompts = [rng.integers(0, m.vocab_size, rng.integers(2, 20)).tolist()
               for _ in range(n_req)]
    n_news = [int(rng.integers(4, 24)) for _ in range(n_req)]
    temps = [0.0 if rng.random() < 0.7 else 1.0 for _ in range(n_req)]
    greedy_idx = [i for i, t in enumerate(temps) if t == 0.0]
    oracle = {i: _sequential(m, params, [prompts[i]], n_news[i])[0]
              for i in greedy_idx}
    engine = ServingEngine(m, params, max_slots=4, max_queue=n_req,
                           steps_per_sync=2)
    handles = [None] * n_req
    errors = []

    def feeder(lo, hi):
        for i in range(lo, hi):
            for _ in range(200):     # ride out transient backpressure
                try:
                    handles[i] = engine.submit(
                        prompts[i], n_news[i], temperature=temps[i])
                    break
                except QueueFullError:
                    time.sleep(0.005)
            else:
                errors.append(i)
            time.sleep(float(rng.random()) * 0.004)

    threads = [threading.Thread(target=feeder,
                                args=(j * 10, (j + 1) * 10))
               for j in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    results = [engine.result(h, timeout=600) for h in handles]
    st = dict(engine.stats)
    met = engine.metrics()
    engine.shutdown()
    for i in greedy_idx:
        np.testing.assert_array_equal(oracle[i], results[i])
    for i, r in enumerate(results):
        assert r.size == len(prompts[i]) + n_news[i]
    assert st["step_traces"] <= 2
    assert met["admitted"] == met["retired"] == n_req


def test_metrics_registry_consistent_after_drain_shutdown():
    """The registry-backed metrics() view, the scheduler's plain
    attributes, and the /metrics exposition all agree once a drain
    shutdown has joined the scheduler thread — no torn reads."""
    from bigdl_tpu import obs
    m, params = _built(seed=11)
    engine = ServingEngine(m, params, max_slots=2)
    handles = [engine.submit(p, 5) for p in PROMPTS[:4]]
    engine.shutdown(drain=True)
    for h in handles:
        assert engine.result(h, timeout=60).size == len(h.prompt) + 5
    met = engine.metrics()
    sch = engine.scheduler
    assert met["admitted"] == sch.admitted == 4
    assert met["retired"] == sch.retired == 4
    assert met["generated_tokens"] == sch.generated_tokens == 20
    assert met["rejected"] == sch.rejected == 0
    assert met["queue_depth"] == 0 and met["slot_occupancy"] == 0
    assert met["time_to_first_token_s"] == pytest.approx(sch.ttft_avg())
    assert met["decode_tokens_per_sec"] == pytest.approx(
        sch.generated_tokens / sch.step_seconds)
    # the /metrics page carries the same numbers under this engine's label
    text = obs.default_registry().prometheus_text()
    lbl = f'{{engine="{engine.obs_label}"}}'
    assert f"bigdl_serving_admitted_total{lbl} 4" in text
    assert f"bigdl_serving_retired_total{lbl} 4" in text
    assert f"bigdl_serving_generated_tokens_total{lbl} 20" in text
    assert f"bigdl_serving_ttft_seconds_count{lbl} 4" in text


def test_metrics_fall_back_to_attributes_when_obs_disabled():
    """With the BIGDL_TPU_OBS kill switch off, metrics() still reports
    true values from the scheduler's plain attributes."""
    from bigdl_tpu import obs
    m, params = _built(seed=12)
    prev = obs.set_enabled(False)
    try:
        with ServingEngine(m, params, max_slots=2) as engine:
            engine.result(engine.submit(PROMPTS[0], 4), timeout=60)
            met = engine.metrics()
        assert met["admitted"] == met["retired"] == 1
        assert met["generated_tokens"] == 4
        assert met["time_to_first_token_s"] > 0
    finally:
        obs.set_enabled(prev)
