"""The benchmark's own arithmetic, on the CPU: traffic, counts, trace reduction."""
import os

import numpy as np
import pytest

from benchmarks.harness import counts, loadgen, peaks, trace_reduce, window

HERE = os.path.dirname(os.path.abspath(__file__))
OPEN = {"loop": "open", "arrivals": "poisson", "rate_per_s": 10.0, "lead_in_s": 2.0,
        "prompt_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.8, "min": 16, "max": 512},
        "output_tokens": {"dist": "lognormal", "median": 96, "sigma": 0.6, "min": 16, "max": 256},
        "sampled_share": 0.2, "temperature": 0.8}


def _key(plan):
    return [(p.due, p.prompt.tolist(), p.max_new, p.temperature) for p in plan]


def test_same_seed_same_requests_other_seed_others():
    a = loadgen.plan(OPEN, 7, 10, 50257)
    b = loadgen.plan(OPEN, 7, 10, 50257)
    c = loadgen.plan(OPEN, 2**31 + 12345, 10, 50257)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


def test_every_seed_gets_the_same_sizes_and_gaps_in_another_order():
    a = loadgen.plan(OPEN, 1, 10, 50257)
    c = loadgen.plan(OPEN, 2, 10, 50257)
    assert len(a) == len(c) == 120                      # 10/s over 2 + 10 s
    for f in (lambda p: len(p.prompt), lambda p: p.max_new, lambda p: p.temperature):
        assert sorted(map(f, a)) == sorted(map(f, c))
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in c]
    full = np.sort(loadgen._gaps(OPEN, 120, np.random.default_rng(0)))
    assert full.sum() == pytest.approx(12.0)
    for plan in (a, c):                                 # every gap is one of the same 120
        assert all(np.abs(full - d).min() < 1e-9 for d in np.diff([p.due for p in plan]))
    assert a[-1].due < 12.0 and c[-1].due < 12.0
    assert sum(p.temperature > 0 for p in a) == 24      # one in five sampled
    assert min(len(p.prompt) for p in a) >= 16 and max(len(p.prompt) for p in a) <= 512


def test_arrivals_the_generator_does_not_know_are_refused():
    with pytest.raises(ValueError):
        loadgen.plan(dict(OPEN, arrivals={"burst": [8, 32]}), 3, 10, 1000)
    with pytest.raises(ValueError):
        loadgen.plan(dict(OPEN, output_tokens={"dist": "fixed", "value": 5}), 3, 10, 1000)


def test_closed_loop_drives_a_fake_engine_and_times_tokens():
    t = {"loop": "closed", "clients": 3, "pool_per_client_s": 20, "lead_in_s": 0.0,
         "prompt_tokens": {"dist": "uniform", "min": 5, "max": 5}, "output_tokens": {"dist": "uniform", "min": 2, "max": 4}}
    plan = loadgen.plan(t, 0, 1, 100)

    def submit(prompt, max_new, temperature):
        return iter(range(max_new))

    gen = loadgen.LoadGenerator(t, plan, submit)
    t0 = gen.start()
    import time
    time.sleep(0.2)
    gen.stop_sending()
    assert gen.drain(10.0) == []
    assert len(gen.records) >= 3
    for r in gen.records:
        assert r.tokens == list(range(r.planned.max_new)) and len(r.token_at) == len(r.tokens)
    win = (t0, t0 + 10.0)
    assert window.tokens_in(gen.records, win) == sum(len(r.tokens) for r in gen.records)
    assert len(window.token_gaps(gen.records, win)) == sum(len(r.tokens) - 1 for r in gen.records)


def test_a_refused_request_misses_every_limit():
    t = dict(OPEN, rate_per_s=50.0, lead_in_s=0.0)
    plan = loadgen.plan(t, 0, 0.2, 100)

    def submit(prompt, max_new, temperature):
        raise RuntimeError("queue full")

    gen = loadgen.LoadGenerator(t, plan, submit)
    t0 = gen.start()
    assert gen.drain(10.0) == []
    delays = window.first_token_delays(gen.records, (t0, t0 + 1.0))
    assert delays and all(d == float("inf") for d in delays)
    assert window.percentile(delays, 95) == float("inf")


def test_percentile_interpolates():
    assert window.percentile([1, 2, 3, 4, 5], 50) == 3
    assert window.percentile(list(range(101)), 95) == 95
    assert window.percentile([], 95) is None


def test_counts_from_shapes():
    assert counts.resnet_forward_macs(50, 224, 1000) == pytest.approx(4.09e9, rel=0.005)
    assert counts.resnet_param_count(50, 1000) == pytest.approx(25.56e6, rel=0.002)
    assert counts.resnet_train_step_flops(256) == 6 * counts.resnet_forward_macs() * 256
    # GPT-2 medium: 355 M as published (biased projections); the program's tree has 98 k fewer
    assert counts.gpt_param_count(50257, 1024, 24, 1024, qkv_bias=True) == pytest.approx(355e6, rel=0.002)
    assert counts.gpt_param_count(50257, 1024, 24, 1024) == 354_724_864
    # a prompt pass counts each token at its own context
    one = counts.gpt_prefill_flops(1, 50257, 1024, 24)
    assert one == counts.gpt_token_flops(1, 1024, 24) + counts.gpt_head_flops(50257, 1024)
    two = counts.gpt_prefill_flops(2, 50257, 1024, 24)
    assert two == (counts.gpt_token_flops(1, 1024, 24) + counts.gpt_token_flops(2, 1024, 24)
                   + counts.gpt_head_flops(50257, 1024))


def test_decode_step_need_counts_live_tokens_only():
    f0, b0 = counts.gpt_decode_step_need(48, 0, 50257, 1024, 24, 1024, 4, 4)
    f1, b1 = counts.gpt_decode_step_need(48, 10000, 50257, 1024, 24, 1024, 4, 4)
    kv = 2 * 24 * 1024 * 4
    assert b1 - b0 == 10000 * kv
    assert b0 == 354_724_864 * 4 + 48 * kv + 48 * 50257 * 4
    # the whole dense table would be 48 * 1024 tokens: the count never charges it
    assert b1 < 354_724_864 * 4 + 48 * 1024 * kv
    assert f1 - f0 == 24 * 4 * 1024 * 10000


def test_peaks_table_refuses_an_unknown_device():
    assert peaks.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


@pytest.fixture(scope="module")
def red():
    return trace_reduce.Reduced.from_json(os.path.join(HERE, "small_trace.json"))


def test_trace_reduction_by_hand(red):
    assert trace_reduce.trace_span(red) == pytest.approx((0.0, 0.045))
    # device 0: 4 + 4 ms in the first step (2 ms hole), the while covers its child, 5 ms of prefill
    assert trace_reduce.busy_seconds(red, 0) == pytest.approx(0.004 + 0.004 + 0.010 + 0.005)
    assert trace_reduce.busy_seconds(red, 1) == pytest.approx(0.020)
    assert trace_reduce.mean_busy_seconds(red) == pytest.approx((0.023 + 0.020) / 2)
    assert trace_reduce.module_durations(red, "jit_step") == pytest.approx([0.010, 0.010])
    assert trace_reduce.module_durations(red, "jit_prefill") == pytest.approx([0.005])
    assert trace_reduce.module_durations(red, "jit_local_step", dev=1) == pytest.approx([0.020])
    assert trace_reduce.module_durations(red, "no_such") == []
    # collectives on device 1: [14, 17) and [16, 18) ms overlap by 1 ms: 4 ms of 20
    assert trace_reduce.collective_share(red, "jit_local_step") == pytest.approx(0.2)
    assert trace_reduce.collective_share(red, "no_such") is None
    names = trace_reduce.module_names(red)
    assert names["jit_step"] == [2, pytest.approx(0.020)]


def test_the_idle_share_is_taken_of_the_traced_interval(red):
    busy = (0.023 + 0.020) / 2
    # without the host's interval: of the span the device events cover (45 ms)
    assert trace_reduce.idle_share(red) == pytest.approx(100 * (1 - busy / 0.045))
    # the profiler ran 60 ms: idle time before the first event and after the last counts
    assert trace_reduce.busy_and_window(red, (10.0, 10.06)) == pytest.approx((busy, 0.06))
    assert trace_reduce.idle_share(red, (10.0, 10.06)) == pytest.approx(100 * (1 - busy / 0.06))
    # an interval read short of the events' own span never makes the device busier than it was
    assert trace_reduce.busy_and_window(red, (10.0, 10.01))[1] == pytest.approx(0.045)


def test_every_share_reads_at_most_100_percent(red):
    span = trace_reduce.trace_span(red)
    for dev in red.devices():
        assert 0 < trace_reduce.busy_seconds(red, dev) <= span[1] - span[0]
    assert 0 <= trace_reduce.collective_share(red, "jit_local_step") <= 1


def test_breakdown_names_what_took_the_time(red):
    top = trace_reduce.top_ops(red, 2)
    # fusion.1 (4 + 3 ms) and fusion.2 (4 ms) differ only in their numbering: one entry
    assert top[0][0].startswith("2x %fusion = f32[8] fusion") and top[0][1] == pytest.approx(0.011)
    assert top[1][0].startswith("%while = ") and top[1][1] == pytest.approx(0.010)
    assert "{" not in top[0][0]
    gaps = dict(trace_reduce.idle_gaps(red))
    # 2 ms inside the first step, 10 ms before the second step, 10 ms before the prefill
    assert sum(gaps.values()) == pytest.approx(0.022)
    assert any(k.startswith("inside jit_step") for k in gaps)
    assert any(k.startswith("before jit_prefill") and "D2H Dispatch" in k for k in gaps)
