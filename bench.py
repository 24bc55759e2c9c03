"""Benchmark: flagship-model training throughput on the real chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} (plus an
``extra`` dict with MFU and the measured matmul roofline for context).

The reference publishes no numbers (BASELINE.md) — its own perf tool is a
dummy-data throughput harness (``models/utils/LocalOptimizerPerf.scala``),
which is exactly what this is, TPU-side. vs_baseline compares against
BENCH_BASELINE.json (the recorded best of the previous round).

Measurement notes:
- NHWC layout + bf16 compute: the TPU-preferred configuration. Measured on
  this chip the framework step runs at ~101% of a hand-written minimal-jax
  ResNet-50 step (scripts/perf_minimal.py), i.e. zero framework overhead;
  the remaining gap to peak is XLA's conv lowering (individual 3x3 convs
  measure 20-40 TFLOP/s on v5e vs ~172 TFLOP/s measured matmul roofline —
  scripts/perf_sweep.py).
- Throughput syncs via a host readback (``float(loss)``) before and after
  the timed loop; like ``block_until_ready`` it returns only when the step
  has finished on the device.
- The TPU only: any other platform is refused, and a leg that fails is
  recorded under its own key and makes the exit code non-zero.
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

# nominal peak bf16 TFLOP/s by device kind (for the MFU figure)
_PEAK_TFLOPS = {"TPU v4": 275e12, "TPU v5 lite": 197e12, "TPU v5": 459e12,
                "TPU v5p": 459e12, "TPU v6 lite": 918e12}

# ResNet-50 fwd FLOPs/image at 224x224 (MACs x 2); train step ~= 3x fwd
_RESNET50_TRAIN_FLOPS = 3 * 4.089e9


def _measure_roofline(size=16384):
    """Measured large-matmul TFLOP/s — the achievable ceiling on this chip."""
    import jax
    import jax.numpy as jnp
    a = jnp.ones((size, size), jnp.bfloat16)
    f = jax.jit(lambda a, b: (a @ b).sum())
    float(f(a, a))
    t0 = time.perf_counter()
    iters = 8
    s = None
    for _ in range(iters):
        s = f(a, a)
    float(s)
    dt = (time.perf_counter() - t0) / iters
    return 2 * size ** 3 / dt


def _leg(extra, key, fn, *args, **kwargs):
    """Run one leg into ``extra[key]``. A leg that raises is printed and
    recorded as ``{"error": ...}`` under its key (``main`` then exits
    non-zero); the remaining legs still run."""
    try:
        extra[key] = fn(*args, **kwargs)
    except Exception as e:
        traceback.print_exc()
        extra[key] = {"error": f"{type(e).__name__}: {e}"[:1000]}
    return extra[key]


def bench_train_throughput(batch=256, iters=30, warmup=5):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu.models.resnet import ResNet
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import make_train_step

    model = ResNet(class_num=1000, depth=50, format="NHWC")
    x_shape = (batch, 224, 224, 3)
    n_class = 1000
    name = "resnet50_train"
    model.build(0, x_shape)
    # zoo models end in LogSoftMax -> ClassNLL is the matching loss
    step_fn = make_train_step(model, nn.ClassNLLCriterion(),
                              SGD(learningrate=0.01, momentum=0.9),
                              compute_dtype=jnp.bfloat16)

    params, state = model.params, model.state
    opt_state = SGD(learningrate=0.01, momentum=0.9).init_state(params)
    rng_np = np.random.default_rng(0)
    x = jnp.asarray(rng_np.standard_normal(x_shape).astype(np.float32))
    y = jnp.asarray(rng_np.integers(0, n_class, batch).astype(np.int32))
    rng = jax.random.key(0)

    for _ in range(warmup):
        params, state, opt_state, loss = step_fn(params, state, opt_state,
                                                 rng, x, y)
    float(loss)  # host readback fully drains the async dispatch queue
    # best of 3 repeats: other tenants of the host only ever slow a run
    # down, so min is the honest estimator
    best_dt = None
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            params, state, opt_state, loss = step_fn(params, state,
                                                     opt_state, rng, x, y)
        float(loss)
        dt = time.perf_counter() - t0
        best_dt = dt if best_dt is None else min(best_dt, dt)
    ips = batch * iters / best_dt

    kind = jax.devices()[0].device_kind
    peak = _PEAK_TFLOPS.get(kind)
    achieved = ips * _RESNET50_TRAIN_FLOPS
    extra = {"achieved_tflops": round(achieved / 1e12, 2)}
    if peak:
        extra["mfu_vs_nominal_peak"] = round(achieved / peak, 4)
    roof = round(_measure_roofline() / 1e12, 1)
    extra["measured_matmul_roofline_tflops"] = roof
    extra["mfu_vs_measured_roofline"] = round(achieved / (roof * 1e12), 4)
    extra["device_kind"] = kind
    extra["batch"] = batch
    _leg(extra, "flash_attention", _bench_flash_attention)
    # phase 1 = the canonical BERT pretrain config (90% of steps run at
    # s128); phase 2 = the long-sequence tail
    _leg(extra, "bert_pretrain", _bench_bert_pretrain,
         batch=128, seq=128, roofline=roof)
    _leg(extra, "bert_pretrain_phase2", _bench_bert_pretrain,
         batch=16, seq=512, roofline=roof)
    _leg(extra, "int8_inference", _bench_int8_inference)
    _leg(extra, "gpt2_decode", _bench_gpt2_decode)
    _leg(extra, "gpt2_serving", _bench_gpt2_serving)
    _leg(extra, "gpt2_serving_max_streams", _bench_gpt2_serving_max_streams)
    _leg(extra, "gpt2_spec", _bench_gpt2_spec)
    _leg(extra, "gpt2_kv_host_tier", _bench_gpt2_kv_host_tier)
    _leg(extra, "gpt2_tp_serving", _bench_gpt2_tp_serving)
    _leg(extra, "gpt2_paged_kernel", _bench_gpt2_paged_kernel)
    _leg(extra, "gpt2_multi_adapter", _bench_gpt2_multi_adapter)
    res = _leg(extra, "resilience", _bench_resilience)
    _leg(res, "fleet_failover", _bench_fleet_failover)
    _leg(extra, "serving_control", _bench_serving_control)
    _leg(extra, "obs_overhead", _bench_obs_overhead)
    _leg(extra, "input_pipeline", _bench_input_pipeline)
    _leg(extra, "train_loop", _bench_train_loop, step_bench_ips=ips)
    return name, ips, extra


def _bench_train_loop(step_bench_ips=None, batch=256, epochs=2,
                      batches_per_epoch=12):
    """Steady-state throughput of the REAL ``DistriOptimizer.optimize``
    loop — feed (MTImageToBatch + Prefetch), dispatch-ahead loss readout,
    triggers, metrics — vs the raw-step figure above.

    VERDICT r4 item 2's acceptance: the loop number within ~2% of the step
    bench (the per-step ``float(loss)`` sync used to make that impossible);
    item 5's: ``feed_wait_frac`` ~ 0 at bench throughput. First
    ``optimize()`` call warms the compile cache; the measured second call
    reports loop wall-clock (data+step buckets) only.
    """
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import (DataSet, MTImageToBatch, Prefetch)
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.optim import SGD, Trigger
    from bigdl_tpu.parallel import DistriOptimizer
    from bigdl_tpu.models.resnet import ResNet
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    rng = np.random.default_rng(0)
    base = rng.integers(0, 255, (256, 256, 256, 3), np.uint8)
    n = batch * batches_per_epoch
    samples = [Sample(base[i % 256], np.float32(i % 1000))
               for i in range(n)]
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))

    def make_opt():
        ds = (DataSet.array(samples)
              >> MTImageToBatch(224, 224, batch,
                                mean=(123., 117., 104.),
                                std=(58., 57., 57.), random_crop=True,
                                random_hflip=True, to_chw=False, seed=0)
              >> Prefetch(4))
        model = ResNet(class_num=1000, depth=50, format="NHWC")
        opt = DistriOptimizer(model=model, dataset=ds,
                              criterion=nn.ClassNLLCriterion(), mesh=mesh,
                              compute_dtype=jnp.bfloat16)
        opt.set_optim_method(SGD(learningrate=0.01, momentum=0.9))
        return opt

    opt = make_opt()
    opt.set_end_when(Trigger.max_epoch(1))
    opt.optimize()            # compile + first-touch warmup
    opt = make_opt()          # fresh metrics, warm XLA cache
    opt.set_end_when(Trigger.max_epoch(epochs))
    opt.optimize()
    m = opt.metrics_summary()
    out = {"images_per_sec": round(m["throughput_rec_s"], 1),
           "feed_wait_frac": round(m["feed_wait_frac"], 4),
           "steps": m["steps"], "batch": batch}
    if step_bench_ips:
        out["vs_step_bench"] = round(m["throughput_rec_s"] / step_bench_ips,
                                     4)
    return out


def _bench_input_pipeline(n=1024, batch=256, hw=256, crop=224, repeats=2,
                          to_chw=False):
    """Host feed rate through the fused record->batch chain
    (MTImageToBatch; BASELINE.md round 4) — must exceed the train
    throughput above or the chip is input-bound. Canonical measurement:
    scripts/perf_input_pipeline.py calls this same function."""
    import os
    import tempfile

    import numpy as np

    from bigdl_tpu.dataset import MTImageToBatch
    from bigdl_tpu.dataset.record_file import (RecordFileDataSet,
                                               write_record_shards)
    from bigdl_tpu.dataset.sample import Sample

    rng = np.random.default_rng(0)
    base = rng.integers(0, 255, (64, hw, hw, 3), np.uint8)
    samples = [Sample(base[i % 64], np.float32(i % 1000)) for i in range(n)]
    workers = min(16, os.cpu_count() or 1)  # MTImageToBatch's own default
    with tempfile.TemporaryDirectory() as d:
        write_record_shards(samples, os.path.join(d, "b"), n_shards=8)
        ds = RecordFileDataSet(os.path.join(d, "b"), process_index=0,
                               process_count=1)
        mt = MTImageToBatch(crop, crop, batch, mean=(123., 117., 104.),
                            std=(58., 57., 57.), random_crop=True,
                            random_hflip=True, to_chw=to_chw, seed=0,
                            workers=workers)
        best = 0.0
        for _ in range(repeats):
            t0 = time.perf_counter()
            cnt = sum(b.real_size
                      for b in mt(ds._iter_samples(train=False)))
            best = max(best, cnt / (time.perf_counter() - t0))
    layout = "CHW" if to_chw else "NHWC"
    return {"config": f"records->fused {layout} batch b{batch}, "
                      f"workers={workers}",
            "images_per_sec": round(best)}


def _bench_int8_inference(batch=256, iters=20):
    """Calibrated int8 serving throughput on ResNet-50 vs the bf16 forward
    — the BigQuant-parity number (reference ``nn/quantized/``). Static
    activation thresholds from a 16-image calibration forward; int8 convs
    ride the MXU's native s8xs8->s32 path and inter-layer activations stay
    bf16 (both measured necessary on v5e — BASELINE.md round 3)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.models.resnet import ResNet
    from bigdl_tpu.nn.quantized import Quantizer

    model = ResNet(class_num=1000, depth=50, format="NHWC")
    model.build(0, (batch, 224, 224, 3))
    model.evaluate()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, 224, 224, 3)), jnp.float32)
    calib = jnp.asarray(rng.standard_normal((16, 224, 224, 3)), jnp.float32)

    def cast(tree, keep=()):
        import jax.tree_util as tu
        return tu.tree_map_with_path(
            lambda p, v: v
            if (not jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating)
                or any(k in str(p) for k in keep))
            else v.astype(jnp.bfloat16), tree)

    p_bf, s_bf = cast(model.params), cast(model.state)
    fwd_bf16 = jax.jit(lambda x: model.apply(
        p_bf, s_bf, x.astype(jnp.bfloat16), training=False)[0])

    qm = Quantizer.quantize(model, calib_input=calib)
    qp = cast(qm.params, keep=("in_scale",))
    qs = cast(qm.state)
    fwd_int8 = jax.jit(lambda x: qm.apply(
        qp, qs, x.astype(jnp.bfloat16), training=False)[0])

    def timeit(f):
        out = f(x)
        float(jnp.sum(out).astype(jnp.float32))
        best = None
        for _ in range(2):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = f(x)
            float(jnp.sum(out).astype(jnp.float32))
            dt = (time.perf_counter() - t0) / iters
            best = dt if best is None else min(best, dt)
        return best

    t_bf16, t_i8 = timeit(fwd_bf16), timeit(fwd_int8)
    a = np.argmax(np.asarray(fwd_bf16(x), np.float32), -1)
    b = np.argmax(np.asarray(fwd_int8(x), np.float32), -1)
    return {"config": f"resnet50 serve b{batch} calibrated int8 vs bf16",
            "int8_images_per_sec": round(batch / t_i8),
            "bf16_images_per_sec": round(batch / t_bf16),
            "speedup_vs_bf16": round(t_bf16 / t_i8, 2),
            "top1_agreement": round(float((a == b).mean()), 4)}


def _bench_gpt2_decode(batch=8, prompt_len=128, n_new=128, repeats=3,
                       model_kwargs=None):
    """KV-cache autoregressive decode throughput on GPT-2 124M: jitted
    prefill + ONE ``lax.scan`` decode dispatch per call (models/gpt.py),
    greedy sampling. The first call compiles both halves; the timed calls
    hit the executable cache, so the number is steady-state serving
    throughput. ``config`` records which model ran."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.models.gpt import gpt2_small

    model = gpt2_small(**(model_kwargs or {}))
    params, _ = model.setup(jax.random.PRNGKey(0), None)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, model.vocab_size,
                                   (batch, prompt_len)), jnp.int32)
    out = model.generate(params, ids, n_new)   # compile prefill + scan
    jax.block_until_ready(out)
    best = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = model.generate(params, ids, n_new)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    stats = model.decode_stats
    return {"config": f"gpt2 vocab{model.vocab_size} "
                      f"L{len(model.gpt.layers)} "
                      f"H{model.gpt.hidden_size} greedy b{batch} "
                      f"prompt{prompt_len} new{n_new}",
            "gpt2_decode_tokens_per_sec": round(batch * n_new / best),
            "prefill_traces": stats["prefill_traces"],
            "decode_traces": stats["decode_traces"],
            "dispatches_per_call": 2}


def _bench_gpt2_serving(n_requests=16, prompt_len=128, n_new=128,
                        repeats=3, rounds=3, max_slots=16,
                        steps_per_sync=8, prefill_window=16,
                        stagger_s=0.0002, admit_wait_s=0.005,
                        model_kwargs=None):
    """Continuous-batching serving throughput (bigdl_tpu/serving) under
    concurrent load: ``n_requests`` closed-loop clients with staggered
    first arrivals, each submitting ``rounds`` generations back-to-back,
    all sharing the engine's slot batch — every decode dispatch advances
    ALL live requests at once. This is the number to compare against
    ``gpt2_decode_tokens_per_sec``, which serializes whole generations
    per ``generate`` call.

    ONE engine serves warmup and every timed wave: jit executables are
    cached per engine (closure identity), so a fresh engine per wave
    would re-time compilation, not serving."""
    import threading

    import numpy as np

    from bigdl_tpu.models.gpt import gpt2_small
    from bigdl_tpu.serving import ServingEngine

    import jax

    model = gpt2_small(**(model_kwargs or {}))
    params, _ = model.setup(jax.random.PRNGKey(0), None)
    rng = np.random.default_rng(0)
    # varied lengths within one prompt bucket: realistic mixed arrivals
    # without extra prefill compilations
    prompts = [rng.integers(0, model.vocab_size,
                            int(rng.integers(prompt_len // 2,
                                             prompt_len + 1)))
               for _ in range(n_requests)]
    engine = ServingEngine(model, params, max_slots=max_slots,
                           max_queue=n_requests,
                           prefill_window=prefill_window,
                           admit_wait_s=admit_wait_s,
                           steps_per_sync=steps_per_sync)

    def wave():
        # one closed-loop client thread per request slot: staggered first
        # arrival, then resubmit-on-completion for ``rounds`` rounds —
        # sustained concurrent load, not a lockstep burst; admit_wait_s
        # lets the engine gather each arrival burst into one prefill
        def client(i):
            time.sleep(i * stagger_s)
            for _ in range(rounds):
                engine.result(engine.submit(prompts[i], n_new),
                              timeout=600)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_requests)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0

    try:
        wave()                         # compiles prefill bucket + step
        best = min(wave() for _ in range(repeats))
        stats = dict(engine.stats)
    finally:
        engine.shutdown()
    return {"config": f"gpt2 vocab{model.vocab_size} "
                      f"L{len(model.gpt.layers)} H{model.gpt.hidden_size} "
                      f"serving {n_requests}req x{rounds} "
                      f"slots{max_slots} "
                      f"window{prefill_window} sync{steps_per_sync} "
                      f"prompt<= {prompt_len} new{n_new}",
            "gpt2_serving_tokens_per_sec": round(
                n_requests * rounds * n_new / best),
            "prefill_traces": stats["prefill_traces"],
            "step_traces": stats["step_traces"],
            "dispatches": stats["dispatches"]}


def _bench_gpt2_serving_max_streams(budget_slots=4, page_size=16,
                                    prompt_len=6, n_new=10,
                                    stream_factor=4, rounds=3,
                                    repeats=2, model_kwargs=None):
    """Paged vs dense K/V at EQUAL HBM budget (docs/serving.md#paged-kv).

    Two engines over one model split the same KV budget of
    ``budget_slots * max_position`` cache tokens: the dense engine spends
    it on ``budget_slots`` worst-case slot rows, the paged engine on a
    page pool (``kv_pages = budget / page_size``) with ``max_slots``
    raised ``stream_factor``-fold. Closed-loop short streams (one page
    each) then measure the peak number of CONCURRENTLY held slots a
    poller observes — the paged engine must sustain >=3x the dense
    number (the performance.md gate; preemptions stay visible in
    ``preempted``). The second leg submits one max-position prompt with
    short requests right behind it and compares the shorts' mean
    client-observed time-to-first-token: chunked prefill keeps the paged
    engine admitting and decoding while the long prompt prefills, where
    the dense engine holds the shorts behind one monolithic dispatch."""
    import threading

    import numpy as np

    from bigdl_tpu.models.gpt import gpt2_small
    from bigdl_tpu.serving import ServingEngine

    import jax

    model = gpt2_small(**(model_kwargs or {}))
    params, _ = model.setup(jax.random.PRNGKey(0), None)
    pmax = model.gpt.max_position
    budget_tokens = budget_slots * pmax
    n_clients = stream_factor * budget_slots
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.vocab_size, prompt_len)
               for _ in range(n_clients)]
    n_new_long = 4
    long_prompt = rng.integers(0, model.vocab_size, pmax - n_new_long)
    shorts = prompts[:budget_slots - 1]    # fit dense slots next to the long

    def max_streams(engine):
        def wave():
            peak = [0]
            stop = threading.Event()

            def poller():
                while not stop.is_set():
                    peak[0] = max(peak[0], engine.slots.occupancy())
                    time.sleep(0.0005)

            def client(i):
                for _ in range(rounds):
                    engine.result(engine.submit(prompts[i], n_new),
                                  timeout=600)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(n_clients)]
            p = threading.Thread(target=poller)
            t0 = time.perf_counter()
            p.start()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            stop.set()
            p.join()
            return peak[0], dt

        wave()                              # compiles prefill + step
        best_peak, best_dt = 0, float("inf")
        for _ in range(repeats):
            pk, dt = wave()
            best_peak, best_dt = max(best_peak, pk), min(best_dt, dt)
        return best_peak, round(n_clients * rounds * n_new / best_dt)

    def short_ttft(engine):
        def probe():
            ttfts = []

            def client(p):
                t0 = time.perf_counter()
                s = engine.stream(engine.submit(p, n_new))
                next(s)
                ttfts.append(time.perf_counter() - t0)
                for _ in s:
                    pass

            h = engine.submit(long_prompt, n_new_long)
            threads = [threading.Thread(target=client, args=(p,))
                       for p in shorts]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            engine.result(h, timeout=600)
            return sum(ttfts) / len(ttfts)

        probe()                       # compiles the long prompt bucket
        return min(probe() for _ in range(repeats))

    dense = ServingEngine(model, params, max_slots=budget_slots,
                          max_queue=n_clients + 4,
                          prefill_window=budget_slots)
    try:
        d_peak, d_tps = max_streams(dense)
        d_ttft = short_ttft(dense)
    finally:
        dense.shutdown()

    # prefix_cache off: distinct prompts anyway, and the stream win being
    # measured is demand paging alone, not page sharing
    paged = ServingEngine(model, params, paged=True, max_slots=n_clients,
                          kv_pages=budget_tokens // page_size,
                          page_size=page_size, prefill_chunk=page_size,
                          prefix_cache=False, max_queue=n_clients + 4,
                          prefill_window=budget_slots)
    try:
        p_peak, p_tps = max_streams(paged)
        p_ttft = short_ttft(paged)
        p_metrics = paged.metrics()
    finally:
        paged.shutdown()

    return {"config": f"gpt2 vocab{model.vocab_size} "
                      f"L{len(model.gpt.layers)} H{model.gpt.hidden_size} "
                      f"kv budget {budget_slots}x{pmax}tok "
                      f"page{page_size} chunk{page_size} "
                      f"{n_clients}clients x{rounds} "
                      f"prompt{prompt_len} new{n_new}",
            "kv_budget_tokens": budget_tokens,
            "dense_max_streams": d_peak,
            "paged_max_streams": p_peak,
            "stream_ratio": round(p_peak / max(1, d_peak), 2),
            "dense_tokens_per_sec": d_tps,
            "paged_tokens_per_sec": p_tps,
            "dense_short_ttft_s": round(d_ttft, 4),
            "paged_short_ttft_s": round(p_ttft, 4),
            "ttft_speedup_under_long_prefill": round(d_ttft / p_ttft, 2),
            "preempted": p_metrics["preempted"],
            "cow_copies": p_metrics["cow_copies"]}


def _bench_gpt2_kv_host_tier(pool_pages=12, page_size=16, n_streams=12,
                             prompt_pages=4, n_new=8, tier_pool_factor=8,
                             model_kwargs=None):
    """Tiered K/V context x concurrency envelope at FIXED HBM (ISSUE 18,
    docs/serving.md#tiered-kv).

    Two paged engines serve the same two-phase multi-session workload
    from the SAME kv page pool: phase one runs ``n_streams`` client
    sessions (each a ``prompt_pages``-page context) through a pool
    holding only ``pool_pages`` pages — a few sessions' worth — and
    phase two resumes every session in order. A session counts toward
    the envelope when its resume is a FULL prefix hit (zero
    re-prefilled tokens, counter-checked per stream). Tier-off, the
    pool's LRU has dropped all but the most recent contexts — and each
    re-prefill evicts more — so almost nothing resumes; tier-on,
    evicted pages demote to pinned host RAM and promote back on
    resume, so the envelope approaches the whole working set (>=4x is
    the acceptance gate, toward the 10x ROADMAP target). Also stamps
    the swap-stall fraction — owner-thread seconds lost to swap
    staging/fetches over decode step seconds, the async-overlap proof
    burden (<10% acceptance): the blocking readback+checksum half of
    every demotion rides the copier thread."""
    import numpy as np

    from bigdl_tpu.models.gpt import gpt2_small
    from bigdl_tpu.serving import ServingEngine
    from bigdl_tpu.serving.paging import kv_token_bytes

    import jax

    model = gpt2_small(**(model_kwargs or {}))
    params, _ = model.setup(jax.random.PRNGKey(0), None)
    prompt_len = prompt_pages * page_size
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.vocab_size, prompt_len)
               for _ in range(n_streams)]
    page_host_bytes = kv_token_bytes(model) * page_size

    def envelope(tier_on):
        eng = ServingEngine(
            model, params, paged=True, max_slots=2,
            kv_pages=pool_pages, page_size=page_size,
            prefill_chunk=2 * page_size, max_queue=n_streams + 4,
            kv_host_tier=tier_on,
            host_tier_bytes=(tier_pool_factor * pool_pages
                             * page_host_bytes),
            host_tier_prefetch=8)
        try:
            for p in prompts:                   # phase 1: populate
                eng.result(eng.submit(p, n_new), timeout=600)
            resumable = 0
            for p in prompts:                   # phase 2: resume all
                before = eng.slots.prefix_miss_tokens
                eng.result(eng.submit(p, n_new), timeout=600)
                if eng.slots.prefix_miss_tokens == before:
                    resumable += 1
            met = eng.metrics()
        finally:
            eng.shutdown()
        stall = float(met.get("host_tier_swap_stall_s", 0.0))
        return resumable, stall, float(eng.scheduler.step_seconds), met

    r_off, _, _, _ = envelope(False)
    r_on, stall, step_s, m_on = envelope(True)
    return {"config": f"gpt2 vocab{model.vocab_size} "
                      f"L{len(model.gpt.layers)} H{model.gpt.hidden_size} "
                      f"pool{pool_pages}p page{page_size} "
                      f"{n_streams}sessions x{prompt_pages}pages "
                      f"new{n_new}",
            "hbm_pool_pages": pool_pages,
            "working_set_pages": n_streams * (prompt_pages + 1),
            "resumable_sessions_tier_off": r_off,
            "resumable_sessions_tier_on": r_on,
            "envelope_tokens_tier_off": r_off * prompt_len,
            "envelope_tokens_tier_on": r_on * prompt_len,
            "envelope_ratio": round(r_on / max(1, r_off), 2),
            "host_tier_demoted_pages": m_on["host_tier_demoted_pages"],
            "host_tier_promoted_pages": m_on["host_tier_promoted_pages"],
            "swap_stall_s": round(stall, 4),
            "decode_step_s": round(step_s, 4),
            "swap_stall_fraction": round(stall / max(step_s, 1e-9), 4)}


def _bench_gpt2_multi_adapter(n_adapters=8, n_requests=48, prompt_len=32,
                              n_new=32, max_slots=24, steps_per_sync=8,
                              lora_rank=4, rounds=3, model_kwargs=None):
    """Multi-tenant LoRA multiplexing vs a single-model engine (ISSUE
    19, docs/serving.md#multi-tenant).

    Two engines serve the same closed-loop workload: the baseline
    serves every request from the base model; the multiplexed engine
    registers ``n_adapters`` LoRA adapters and spreads the SAME
    requests round-robin across the tenants, so every decode dispatch
    is a mixed batch gathering per-slot adapter slabs inside the one
    executable. Aggregate tokens/sec of the multiplexed engine must
    stay >=0.8x the single-model engine (the acceptance bar on the
    batched-gather overhead). The default batch is deliberately wide
    (``max_slots=24``): the per-slot gather + rank-r delta ops are
    dispatch-bound, so their cost amortizes across decode rows while
    base-matmul compute grows — a skinny batch on a micro model
    overstates overhead that is negligible at real scale. Adapter-swap
    latency — pool cold-load
    wall time per adapter, ladder fetch and jitted device write
    included — is reported alongside: the price a tenant pays once per
    residency, never per token."""
    import numpy as np

    from bigdl_tpu.models.gpt import gpt2_small
    from bigdl_tpu.models.lora import init_adapter
    from bigdl_tpu.serving import ServingEngine

    import jax

    model = gpt2_small(**(model_kwargs or {}))
    params, _ = model.setup(jax.random.PRNGKey(0), None)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.vocab_size, prompt_len)
               for _ in range(n_requests)]
    adapters = {
        f"tenant{i}": init_adapter(jax.random.PRNGKey(100 + i), params,
                                   lora_rank, b_std=0.02)
        for i in range(n_adapters)}

    def build(multi):
        kw = (dict(lora=True, lora_rank=lora_rank,
                   adapter_slots=n_adapters, adapters=adapters)
              if multi else {})
        return ServingEngine(model, params, max_slots=max_slots,
                             max_queue=n_requests + 4,
                             steps_per_sync=steps_per_sync, **kw)

    def one_round(eng, multi):
        t0 = time.perf_counter()
        hs = [eng.submit(p, n_new,
                         adapter=(f"tenant{i % n_adapters}"
                                  if multi else None))
              for i, p in enumerate(prompts)]
        toks = sum(int(np.asarray(eng.result(h, timeout=600)).size)
                   for h in hs) - sum(p.size for p in prompts)
        return toks / (time.perf_counter() - t0)

    # both engines live at once, rounds interleaved single/multi, so
    # machine drift between separate phases cannot skew the ratio
    base_eng, multi_eng = build(False), build(True)
    base_tps = multi_tps = 0.0
    try:
        one_round(base_eng, False)    # warmup: compiles
        one_round(multi_eng, True)    # warmup: compiles + cold loads
        for _ in range(rounds):
            base_tps = max(base_tps, one_round(base_eng, False))
            multi_tps = max(multi_tps, one_round(multi_eng, True))
        met = multi_eng.metrics()
    finally:
        base_eng.shutdown()
        multi_eng.shutdown()
    loads = int(met.get("adapter_loads", 0))
    swap_s = float(met.get("adapter_swap_seconds", 0.0))
    return {"config": f"gpt2 vocab{model.vocab_size} "
                      f"L{len(model.gpt.layers)} H{model.gpt.hidden_size} "
                      f"{n_adapters}adapters r{lora_rank} "
                      f"{n_requests}req p{prompt_len} new{n_new}",
            "n_adapters": n_adapters,
            "single_model_tokens_per_sec": round(base_tps, 1),
            "multi_adapter_tokens_per_sec": round(multi_tps, 1),
            "throughput_ratio": round(multi_tps / max(base_tps, 1e-9), 3),
            "adapter_cold_loads": loads,
            "adapter_swap_s_per_load": round(swap_s / max(1, loads), 4),
            "adapter_pool_hits": int(met.get("adapter_hits", 0)),
            "adapter_evictions": int(met.get("adapter_evictions", 0))}


def _bench_gpt2_tp_serving(tp=2, pool_pages_per_chip=16, page_size=8,
                           prompt_len=12, n_new=4, rounds=3, repeats=2,
                           model_kwargs=None):
    """Tensor-parallel serving at EQUAL PER-CHIP KV budget (ISSUE 15,
    docs/serving.md#sharded-serving).

    Two paged engines serve the same closed-loop workload from the same
    per-chip byte budget: the tp=1 engine's pool holds
    ``pool_pages_per_chip`` pages, while the tp=N engine shards every
    page's head axis N ways so the SAME per-chip bytes hold
    ``N x pool_pages_per_chip`` global pages. Prompt and budget are
    sized so each stream pins exactly ``(prompt+new)/page`` pages for
    its whole life (no growth preemption), making peak concurrently
    held slots a direct read of pool capacity — it must scale ~N-fold
    (>=1.8x at N=2 is the acceptance bar). Tokens/sec is reported for
    both engines."""
    import threading

    import numpy as np

    from bigdl_tpu.models.gpt import gpt2_small
    from bigdl_tpu.serving import ServingEngine
    from bigdl_tpu.serving.paging import kv_token_bytes

    import jax

    if jax.device_count() < tp:
        return {"skipped": f"needs {tp} devices, have {jax.device_count()}"}

    model = gpt2_small(**(model_kwargs or {}))
    params, _ = model.setup(jax.random.PRNGKey(0), None)
    per_tok = kv_token_bytes(model)
    budget = pool_pages_per_chip * page_size * per_tok   # per-chip bytes
    pages_per_stream = -(-(prompt_len + n_new) // page_size)
    cap_tp = tp * pool_pages_per_chip // pages_per_stream
    n_clients = cap_tp + cap_tp // 2      # oversubscribe the bigger pool
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.vocab_size, prompt_len)
               for _ in range(n_clients)]

    def max_streams(engine):
        def wave():
            peak = [0]
            stop = threading.Event()

            def poller():
                while not stop.is_set():
                    peak[0] = max(peak[0], engine.slots.occupancy())
                    time.sleep(0.0005)

            def client(i):
                for _ in range(rounds):
                    engine.result(engine.submit(prompts[i], n_new),
                                  timeout=600)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(n_clients)]
            p = threading.Thread(target=poller)
            t0 = time.perf_counter()
            p.start()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            stop.set()
            p.join()
            return peak[0], dt

        wave()                              # compiles prefill + step
        best_peak, best_dt = 0, float("inf")
        for _ in range(repeats):
            pk, dt = wave()
            best_peak, best_dt = max(best_peak, pk), min(best_dt, dt)
        return best_peak, round(n_clients * rounds * n_new / best_dt)

    out = {"config": f"gpt2 vocab{model.vocab_size} "
                     f"L{len(model.gpt.layers)} H{model.gpt.hidden_size} "
                     f"heads{model.gpt.layers[0].attn.n_heads} "
                     f"page{page_size} {pool_pages_per_chip}pages/chip "
                     f"{n_clients}clients x{rounds} "
                     f"prompt{prompt_len} new{n_new}",
           "kv_budget_bytes_per_chip": budget}
    for t in (1, tp):
        eng = ServingEngine(model, params, paged=True, kv_bytes=budget,
                            page_size=page_size, tp=t,
                            max_slots=n_clients, prefix_cache=False,
                            max_queue=n_clients + 4, prefill_window=4)
        try:
            st = eng.slots.pool_stats()
            peak, tps = max_streams(eng)
        finally:
            eng.shutdown()
        out[f"tp{t}_num_pages"] = st["num_pages"]
        out[f"tp{t}_kv_bytes_per_token_per_chip"] = \
            st["kv_bytes_per_token_per_chip"]
        out[f"tp{t}_max_streams"] = peak
        out[f"tp{t}_tokens_per_sec"] = tps
    out["stream_ratio"] = round(out[f"tp{tp}_max_streams"]
                                / max(1, out["tp1_max_streams"]), 2)
    return out


def _bench_gpt2_paged_kernel(n_requests=6, prompt_len=24, n_new=16,
                             page_size=8, model_kwargs=None):
    """Pallas paged-attention kernel vs the XLA gather path
    (BIGDL_TPU_PAGED_KERNEL; docs/performance.md#paged-attention-kernel)
    on fp32, int8 and tp=2 paged engines.

    Every variant asserts temperature-0 token identity against its
    flag-off twin before its ratio is recorded."""
    import numpy as np

    from bigdl_tpu.models.gpt import gpt2_small
    from bigdl_tpu.serving import ServingEngine

    import jax

    rng = np.random.default_rng(0)
    mk = model_kwargs or {}
    vocab = mk.get("vocab_size", 50257)
    prompts = [rng.integers(0, vocab, prompt_len)
               for _ in range(n_requests)]

    def run(flag_on, **ekw):
        # the flag is read at model construction: a fresh model (same
        # seed -> identical params) per side keeps the two engines'
        # jitted closures honestly separate
        old = os.environ.get("BIGDL_TPU_PAGED_KERNEL")
        os.environ["BIGDL_TPU_PAGED_KERNEL"] = "1" if flag_on else "0"
        try:
            model = gpt2_small(**mk)
            params, _ = model.setup(jax.random.PRNGKey(0), None)
            eng = ServingEngine(model, params, max_slots=n_requests,
                                max_queue=n_requests + 2, paged=True,
                                page_size=page_size, **ekw)
            try:
                handles = [eng.submit(p, n_new) for p in prompts]
                [eng.result(h, timeout=600) for h in handles]  # compile
                t0 = time.perf_counter()
                handles = [eng.submit(p, n_new) for p in prompts]
                outs = [np.asarray(eng.result(h, timeout=600))
                        for h in handles]
                dt = time.perf_counter() - t0
            finally:
                eng.shutdown()
            return outs, n_requests * n_new / dt
        finally:
            if old is None:
                os.environ.pop("BIGDL_TPU_PAGED_KERNEL", None)
            else:
                os.environ["BIGDL_TPU_PAGED_KERNEL"] = old

    out = {"config": f"paged kernel vs XLA gather, {n_requests}req "
                     f"prompt{prompt_len} new{n_new} page{page_size}"}
    variants = [("fp32", {}), ("int8", {"int8_kv": True})]
    if jax.device_count() >= 2:
        variants.append(("tp2", {"tp": 2}))
    else:
        out["tp2"] = {"skipped": f"needs 2 devices, "
                                 f"have {jax.device_count()}"}
    for name, ekw in variants:
        xla_outs, xla_tps = run(False, **ekw)
        kern_outs, kern_tps = run(True, **ekw)
        parity = all(np.array_equal(a, b)
                     for a, b in zip(xla_outs, kern_outs))
        if not parity:
            raise AssertionError(
                f"paged kernel variant {name} diverged from the XLA "
                f"gather path at temperature 0")
        out[name] = {"parity": True,
                     "xla_tokens_per_sec": round(xla_tps),
                     "kernel_tokens_per_sec": round(kern_tps),
                     "kernel_vs_xla_ratio": round(kern_tps / xla_tps, 3)}
    return out


def _bench_gpt2_spec(n_requests=8, prompt_len=32, n_new=256, repeats=2,
                     rounds=2, max_slots=8, steps_per_sync=4,
                     spec_tokens=4, model_kwargs=None):
    """Speculative serving throughput vs the sequential engine on the
    SAME repetitive workload (docs/serving.md#speculative-decoding).

    Prompts are tiled short motifs of DISTINCT tokens, so the streams
    settle into cyclic continuations the n-gram draft predicts well
    (a repeated token inside the motif would make its bigram successor
    ambiguous and cap the chained accept) — the bar is >=1.5x the
    sequential serving number at an accept rate >=0.5 (generations
    must be long enough to amortize the unsettled early phase; the
    rate climbs with stream length).
    Different motifs per client: prefix sharing must not hide prefill
    cost differences, and the draft has to learn each stream's cycle
    on its own. A third engine stacks int8 weights under speculation
    (``gpt2_spec_int8_tokens_per_sec``) — the memory-traffic saving
    and the dispatch saving are independent and must compose.

    Speculation trades dispatches and weight traffic for redundant
    verify FLOPs, so it pays only where decode is weight-bound (a
    gamma-wide verify then streams the same bytes as a one-token
    step); a model shrunk into the compute-bound regime makes the
    speedup physically unreachable."""
    import threading

    import numpy as np

    from bigdl_tpu.models.gpt import gpt2_small
    from bigdl_tpu.serving import ServingEngine

    import jax

    model = gpt2_small(**(model_kwargs or {}))
    params, _ = model.setup(jax.random.PRNGKey(0), None)
    rng = np.random.default_rng(0)
    prompts = []
    for _ in range(n_requests):
        motif = rng.choice(model.vocab_size, 4, replace=False)
        prompts.append(np.tile(motif, prompt_len // 4 + 1)[:prompt_len]
                       .astype(np.int32))

    def run(spec, int8=False):
        engine = ServingEngine(model, params, max_slots=max_slots,
                               max_queue=n_requests + 4,
                               prefill_window=max_slots,
                               steps_per_sync=steps_per_sync,
                               spec_tokens=spec, int8_weights=int8)

        def wave():
            def client(i):
                for _ in range(rounds):
                    engine.result(engine.submit(prompts[i], n_new),
                                  timeout=600)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(n_requests)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return time.perf_counter() - t0

        try:
            wave()                     # compiles prefill bucket + step
            best = min(wave() for _ in range(repeats))
            met = engine.metrics()
        finally:
            engine.shutdown()
        return n_requests * rounds * n_new / best, met

    base_tps, _ = run(1)
    spec_tps, met = run(spec_tokens)
    int8_tps, int8_met = run(spec_tokens, int8=True)
    return {"config": f"gpt2 vocab{model.vocab_size} "
                      f"L{len(model.gpt.layers)} H{model.gpt.hidden_size} "
                      f"spec gamma{spec_tokens} sync{steps_per_sync} "
                      f"{n_requests}req x{rounds} repetitive "
                      f"prompt{prompt_len} new{n_new}",
            "gpt2_serving_tokens_per_sec": round(base_tps),
            "gpt2_spec_tokens_per_sec": round(spec_tps),
            "spec_speedup": round(spec_tps / base_tps, 2),
            "spec_accept_rate": round(met["spec_accept_rate"], 3),
            "spec_proposed": met["spec_proposed"],
            "spec_rollbacks": met["spec_rollbacks"],
            "gpt2_spec_int8_tokens_per_sec": round(int8_tps),
            "int8_spec_accept_rate": round(
                int8_met["spec_accept_rate"], 3),
            "step_traces": met["step_traces"]}


def _bench_resilience(n_requests=8, prompt_len=32, n_new=32,
                      repeats=3, rounds=3, max_slots=8,
                      model_kwargs=None):
    """Serving goodput under injected faults (docs/resilience.md).

    Three numbers off one engine: clean-wave goodput, goodput with a
    canned fault plan forcing scheduler recoveries mid-wave (every
    caller still gets its tokens — re-prefill makes the faults
    invisible, only slower), and the disarmed harness's cost per
    ``fault_point`` — the plan-is-None fast path every serving step
    pays — expressed against the clean per-token budget (<1% is the
    bar). ``recovery_s`` amortizes the whole chaos slowdown over the
    recoveries that caused it: rebuild + re-prefill of every live slot.

    ``recovery_speedup`` measures the crash-consistent recovery path
    (docs/resilience.md#crash-consistent-recovery): the same long-prompt
    many-stream wave served by a fresh engine off a warm KV page
    snapshot store (restore: digest-addressed page loads + logits-only
    replay) vs off a cold store (full re-prefill) — the O(restore) vs
    O(recompute) claim as a single ratio."""
    import threading

    import numpy as np

    from bigdl_tpu.models.gpt import gpt2_small
    from bigdl_tpu.resilience import faults
    from bigdl_tpu.serving import ServingEngine

    import jax

    model = gpt2_small(**(model_kwargs or {}))
    params, _ = model.setup(jax.random.PRNGKey(0), None)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.vocab_size, prompt_len)
               for _ in range(n_requests)]
    engine = ServingEngine(model, params, max_slots=max_slots,
                           max_queue=n_requests)

    def wave():
        def client(i):
            for _ in range(rounds):
                engine.result(engine.submit(prompts[i], n_new),
                              timeout=600)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_requests)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0

    tokens = n_requests * rounds * n_new
    try:
        wave()                          # compiles prefill bucket + step
        clean = min(wave() for _ in range(repeats))
        # disarmed fast path: what every step pays when no plan is armed
        calls = 100_000
        t0 = time.perf_counter()
        for _ in range(calls):
            faults.fault_point("serving.step")
        per_call_s = (time.perf_counter() - t0) / calls
        before = engine.metrics()["recoveries"]
        faults.configure("seed=3;serving.step:error:every=40:times=3")
        try:
            chaos = wave()
        finally:
            faults.configure(None)
        recoveries = engine.metrics()["recoveries"] - before
    finally:
        engine.shutdown()
    per_token_clean = clean / tokens
    out = {"config": f"gpt2 vocab{model.vocab_size} "
                     f"L{len(model.gpt.layers)} H{model.gpt.hidden_size} "
                     f"serving {n_requests}req x{rounds} new{n_new}, "
                     f"plan: serving.step error every=40 times=3",
           "goodput_clean_tokens_per_sec": round(tokens / clean),
           "goodput_chaos_tokens_per_sec": round(tokens / chaos),
           "recoveries": recoveries,
           "recovery_s": round((chaos - clean) / max(recoveries, 1), 4),
           "disarmed_fault_point_ns": round(per_call_s * 1e9),
           "disarmed_overhead_vs_token_budget": round(
               per_call_s / per_token_clean, 4)}
    out.update(_bench_recovery_speedup())
    return out


def _bench_recovery_speedup(n_streams=8, prompt_len=192,
                            model_kwargs=None):
    """Restore-based vs re-prefill recovery of a long-prompt
    many-stream wave (the test twin is
    tests/test_snapshot.py::TestRecoverySpeed). Two timed passes over
    identical prompts on fresh engines: one against the page store a
    first pass populated (restore), one against a cold store
    (re-prefill)."""
    import shutil
    import tempfile

    import numpy as np

    from bigdl_tpu.models.gpt import GPTForCausalLM
    from bigdl_tpu.serving import ServingEngine

    import jax

    kw = dict(vocab_size=61, hidden_size=128, n_layers=4, n_heads=4,
              max_position=256)
    kw.update(model_kwargs or {})
    model = GPTForCausalLM(**kw)
    params, _ = model.setup(jax.random.PRNGKey(0), None)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.vocab_size, prompt_len)
               for _ in range(n_streams)]
    warm = rng.integers(0, model.vocab_size, prompt_len)

    def run(snap_dir):
        eng = ServingEngine(model, params, max_slots=n_streams,
                            paged=True, kv_pages=20 * n_streams,
                            page_size=16, prefill_chunk=32,
                            kv_snapshot=True, snapshot_dir=snap_dir,
                            snapshot_interval_s=0.0)
        try:
            eng.result(eng.submit(warm, 2), timeout=600)   # compile
            t0 = time.perf_counter()
            for h in [eng.submit(p, 2) for p in prompts]:
                eng.result(h, timeout=600)
            dt = time.perf_counter() - t0
            assert eng.shutdown(drain=True)
        finally:
            eng.shutdown(drain=False)
        return dt

    store = tempfile.mkdtemp(prefix="bigdl-bench-snap-")
    cold = tempfile.mkdtemp(prefix="bigdl-bench-cold-")
    try:
        run(store)                       # populate the page store
        t_restore = run(store)
        t_reprefill = run(cold)
    finally:
        shutil.rmtree(store, ignore_errors=True)
        shutil.rmtree(cold, ignore_errors=True)
    return {"recovery_restore_s": round(t_restore, 4),
            "recovery_reprefill_s": round(t_reprefill, 4),
            "recovery_speedup": round(t_reprefill / t_restore, 2)}


def _bench_serving_control(prompt_len=32, n_new=32, max_slots=4,
                           n_interactive=12, n_batch=64, batch_clients=4,
                           model_kwargs=None):
    """Mixed-tier overload through the serving control plane
    (docs/serving.md#control-plane).

    One autoscaling fleet behind SLO-aware admission serves an
    interactive client while ``batch_clients`` greedy best-effort
    clients flood it. The contract being measured: interactive p99 TTFT
    holds within 1.5x its unloaded value because best-effort traffic is
    shed/queued behind it (never the reverse), and the autoscaler grows
    the fleet under the flood and retires the extra replica at idle —
    with every shed/scale event visible on the obs registry."""
    import threading

    import numpy as np

    from bigdl_tpu.models.gpt import gpt2_small
    from bigdl_tpu.serving import (AutoScaler, ControlPolicy, EngineFleet,
                                   QueueFullError, ServingEngine)

    import jax

    model = gpt2_small(**(model_kwargs or {}))
    params, _ = model.setup(jax.random.PRNGKey(0), None)
    rng = np.random.default_rng(0)
    i_prompts = [rng.integers(0, model.vocab_size, prompt_len)
                 for _ in range(4)]
    b_prompts = [rng.integers(0, model.vocab_size, prompt_len)
                 for _ in range(8)]
    policy_kw = dict(slo_ttft_s={"interactive": 30.0, "standard": 5.0,
                                 "best_effort": 0.75},
                     base_ttft_s=0.05)

    def factory():
        # each replica gets its OWN policy: token buckets and fair-queue
        # state are per-engine. Warm the prefill + step executables
        # before the replica joins the fleet so a mid-flood scale-up
        # never serves interactive traffic off a cold compile.
        eng = ServingEngine(model, params, max_slots=max_slots,
                            max_queue=16, policy=ControlPolicy(**policy_kw))
        eng.result(eng.submit(i_prompts[0], 2), timeout=300)
        return eng

    fleet = EngineFleet(factory, replicas=1)
    # fast poll + shallow depth threshold: admission shedding keeps the
    # queue deliberately short, so the scale-up signal must trip on the
    # backlog that remains inside the ~2s flood window
    scaler = AutoScaler(fleet, min_replicas=1, max_replicas=2,
                        poll_interval_s=0.15, up_queue_depth=3.0,
                        votes_to_scale=2, idle_polls_to_retire=4,
                        cooldown_s=1.0)

    def ttft_p99(handles):
        samples = sorted((h.first_token_at - h.submitted_at)
                         for h in handles
                         if h.first_token_at is not None)
        if not samples:
            return None
        return samples[min(len(samples) - 1,
                           int(0.99 * (len(samples) - 1)))]

    shed_submit = [0] * batch_clients
    done_batch = [0] * batch_clients
    stop_batch = threading.Event()

    def batch_client(ci):
        k = 0
        while not stop_batch.is_set() and k < n_batch:
            # burst of 4 in flight per client: an open-ish loop that
            # actually builds a backlog (a strict closed loop never
            # exercises queueing or the autoscaler)
            handles = []
            for _ in range(min(4, n_batch - k)):
                k += 1
                try:
                    handles.append(fleet.submit(
                        b_prompts[(ci + k) % len(b_prompts)], n_new,
                        priority="best_effort", client_id=f"batch-{ci}"))
                except QueueFullError:  # shed or backpressured: move on
                    shed_submit[ci] += 1
            for h in handles:
                try:
                    h.result(timeout=120)
                    done_batch[ci] += 1
                except Exception:
                    shed_submit[ci] += 1   # shed from the queue post-admit

    def interactive_wave():
        handles = []
        for k in range(n_interactive):
            h = fleet.submit(i_prompts[k % len(i_prompts)], n_new,
                             priority="interactive", client_id="human")
            h.result(timeout=120)
            handles.append(h)
        return handles

    try:
        interactive_wave()              # compile prefill bucket + step
        unloaded = ttft_p99(interactive_wave())
        scaler.start()
        threads = [threading.Thread(target=batch_client, args=(ci,))
                   for ci in range(batch_clients)]
        for t in threads:
            t.start()
        time.sleep(0.5)                 # let the flood build a backlog
        loaded = ttft_p99(interactive_wave())
        stop_batch.set()
        for t in threads:
            t.join()
        # drain to idle and give the autoscaler time to retire. The
        # flood can end while the scale-up is still building its
        # replica (not yet published, so replica_count() is still 1);
        # scale_ups only increments once the build lands, so wait for
        # the pending action to surface before watching for the retire.
        deadline = time.perf_counter() + 30.0
        while (time.perf_counter() < deadline
               and (scaler.scale_ups == 0
                    or fleet.replica_count() > 1)):
            time.sleep(0.25)
        shed_queued = sum(m.get("shed", 0)
                          for m in fleet.metrics().values())
    finally:
        scaler.stop()
        fleet.close()
    submitted = batch_clients * n_batch
    completed = sum(done_batch)
    return {"config": f"gpt2 vocab{model.vocab_size} "
                      f"L{len(model.gpt.layers)} H{model.gpt.hidden_size} "
                      f"{batch_clients} best_effort clients x{n_batch} vs "
                      f"1 interactive, fleet 1..2 replicas",
            "interactive_ttft_p99_unloaded_ms": round(unloaded * 1e3, 2),
            "interactive_ttft_p99_overload_ms": round(loaded * 1e3, 2),
            "interactive_p99_ratio": round(loaded / unloaded, 2),
            # 1.5x the unloaded p99, floored at one decode-step quantum
            # (an idle-machine baseline is sub-ms on small models; the
            # floor absorbs the irreducible wait for the in-flight
            # dispatch that ANY arrival pays on a busy engine)
            "slo_held": loaded <= max(1.5 * unloaded, 0.05),
            "best_effort_submitted": submitted,
            "best_effort_completed": completed,
            "best_effort_shed": submitted - completed,
            "best_effort_shed_queued": shed_queued,
            "autoscaler_scale_ups": scaler.scale_ups,
            "autoscaler_scale_downs": scaler.scale_downs}


def _bench_fleet_failover(n_requests=12, prompt_len=24, n_new=48,
                          replicas=3, model_kwargs=None):
    """Cross-replica failover (docs/resilience.md#fleet-failover).

    The same wave is served twice by a 3-replica fleet whose replicas
    share one KV snapshot store, and in each run the busiest replica is
    killed mid-decode. Without failover its in-flight streams are
    simply lost (``failed_without_failover``); with failover they
    migrate to the survivors — restore-vs-reprefill split reported —
    and the whole wave completes. ``steady_state_s`` is
    kill-to-last-token on the failover fleet; decode is paced with a
    small injected per-step delay so the kill reliably lands
    mid-flight on the tiny CPU model (the pacing is identical in both
    runs, so the with/without comparison stays apples-to-apples)."""
    import tempfile
    import time as _time

    import numpy as np

    from bigdl_tpu.models.gpt import gpt2_small
    from bigdl_tpu.resilience import faults
    from bigdl_tpu.serving import EngineFleet, ServingEngine

    import jax

    model = gpt2_small(**(model_kwargs or {}))
    params, _ = model.setup(jax.random.PRNGKey(0), None)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.vocab_size, prompt_len).tolist()
               for _ in range(n_requests)]

    def run(failover, root):
        def factory(replica_id=0):
            return ServingEngine(
                model, params, max_slots=4, paged=True, page_size=8,
                kv_pages=256, prefix_cache=True, kv_snapshot=True,
                snapshot_dir=root, snapshot_interval_s=0.02,
                snapshot_journal=f"journal-{replica_id}.jsonl")

        fleet = EngineFleet(factory, replicas=replicas, route_block=8,
                            failover=failover, probation_s=60.0,
                            rebuild_budget_s=60.0, health_poll_s=0.05,
                            supervisor_kw=dict(submit_wait_s=30.0))
        try:
            for h in [fleet.submit(p, 2) for p in prompts]:
                h.result(120)                       # warm compiles
            rid_of = [fleet._pick(p).rid for p in prompts]
            victim = max(set(rid_of), key=rid_of.count)
            faults.configure("seed=0;serving.step:delay=0.002")
            handles = [fleet.submit(p, n_new) for p in prompts]
            deadline = _time.monotonic() + 120
            while (not all(len(h.tokens) >= 2 for h in handles)
                   and _time.monotonic() < deadline):
                _time.sleep(0.002)
            t_kill = _time.monotonic()
            lost_ids = set()
            if failover:
                fleet.evacuate_replica(victim)
            else:
                rep = next(r for r in fleet._replicas
                           if r.rid == victim)
                lost_ids = {r.id for r in rep.sup.evacuate()}
            failed = 0
            for h in handles:
                if h.id in lost_ids:
                    failed += 1                     # nobody adopts it
                    continue
                try:
                    h.result(120)
                except BaseException:
                    failed += 1
            steady = _time.monotonic() - t_kill
            return {"failed": failed,
                    "migrated": fleet.migrated_streams,
                    "restored": fleet.failover_restored,
                    "reprefilled": fleet.failover_reprefilled,
                    "steady_state_s": round(steady, 3)}
        finally:
            faults.configure(None)
            fleet.close(drain=False)

    with tempfile.TemporaryDirectory() as d1:
        off = run(False, d1)
    with tempfile.TemporaryDirectory() as d2:
        on = run(True, d2)
    return {"config": f"gpt2 vocab{model.vocab_size} "
                      f"L{len(model.gpt.layers)} H{model.gpt.hidden_size} "
                      f"{replicas} replicas, {n_requests} streams x"
                      f"{n_new} tokens, busiest replica killed",
            "failed_without_failover": off["failed"],
            "failed_with_failover": on["failed"],
            "migrated_streams": on["migrated"],
            "restored_streams": on["restored"],
            "reprefilled_streams": on["reprefilled"],
            "steady_state_s": on["steady_state_s"]}


def _bench_bert_pretrain(batch=128, seq=128, iters=20, warmup=3,
                         roofline=None, use_flash=None):
    """End-to-end BERT-Base MLM pretrain step MFU — the compute-bound
    flagship number. Framework path: BertForMLM + CrossEntropyCriterion +
    Adam through make_train_step, bf16 compute, attention kernel
    auto-selected (parallel/sequence.py flash_profitable). Default is the
    canonical phase-1 config (b128 s128: 0.55 nominal MFU / 0.75 of the
    measured roofline on v5e); the s512 phase-2 config runs as a second
    entry (0.50/0.66)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu.models.transformer import (BertForMLM,
                                              bert_mlm_flops_per_token)
    from bigdl_tpu.optim import Adam
    from bigdl_tpu.optim.optimizer import make_train_step

    model = BertForMLM(max_position=max(512, seq))
    if use_flash is not None:  # sweep override; None = framework auto
        for lyr in model.bert.layers:
            lyr.attn.use_flash = use_flash
    model.build(0, (batch, seq))
    opt = Adam(learningrate=1e-4)
    step = make_train_step(model, nn.CrossEntropyCriterion(), opt,
                           compute_dtype=jnp.bfloat16)
    params, state = model.params, model.state
    opt_state = opt.init_state(params)
    rng_np = np.random.default_rng(0)
    x = jnp.asarray(rng_np.integers(0, 30522, (batch, seq)), jnp.int32)
    y = jnp.asarray(rng_np.integers(0, 30522, batch * seq), jnp.int32)
    rng = jax.random.key(0)
    for _ in range(warmup):
        params, state, opt_state, loss = step(params, state, opt_state,
                                              rng, x, y)
    float(loss)
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            params, state, opt_state, loss = step(params, state,
                                                  opt_state, rng, x, y)
        float(loss)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    tok_s = batch * seq * iters / best
    achieved = tok_s * 3 * bert_mlm_flops_per_token(s=seq)
    out = {"config": f"BERT-Base MLM b{batch} s{seq} bf16 Adam",
           "tokens_per_sec": round(tok_s),
           "achieved_tflops": round(achieved / 1e12, 1)}
    kind = jax.devices()[0].device_kind
    peak = _PEAK_TFLOPS.get(kind)
    if peak:
        out["mfu_vs_nominal_peak"] = round(achieved / peak, 4)
    if roofline:
        out["mfu_vs_measured_roofline"] = round(
            achieved / (roofline * 1e12), 4)
    return out


def _bench_flash_attention(b=1, h=8, s=8192, d=64, iters=8):
    """Pallas flash kernel vs XLA fused attention, causal fwd+bwd — the
    hot-op kernel comparison recorded alongside the headline number."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(0)
    q, k, v = [jnp.asarray(rng.standard_normal((b, h, s, d)),
                           dtype=jnp.bfloat16) for _ in range(3)]

    def ref(q, k, v):
        sc = d ** -0.5
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * sc
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask, scores, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd",
                          jax.nn.softmax(scores, -1), v)

    ga = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=True).astype(jnp.float32)),
        argnums=(0, 1, 2)))
    gr = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        ref(q, k, v).astype(jnp.float32)), argnums=(0, 1, 2)))

    def timeit(f):
        r = f(q, k, v)
        float(jnp.sum(r[0]).astype(jnp.float32))
        best = None
        for _ in range(2):
            t0 = time.perf_counter()
            for _ in range(iters):
                r = f(q, k, v)
            float(jnp.sum(r[0]).astype(jnp.float32))
            dt = (time.perf_counter() - t0) / iters
            best = dt if best is None else min(best, dt)
        return best

    t_flash, t_xla = timeit(ga), timeit(gr)
    return {"config": f"causal b{b} h{h} s{s} d{d} bf16 fwd+bwd",
            "pallas_ms": round(t_flash * 1e3, 2),
            "xla_ms": round(t_xla * 1e3, 2),
            "speedup": round(t_xla / t_flash, 2)}


def _env_metadata():
    """jax/jaxlib versions + device identity for the BENCH artifact, so
    perf trajectories stay attributable across environment changes."""
    import platform

    import jax
    import jaxlib
    devs = jax.devices()
    return {"jax_version": jax.__version__,
            "jaxlib_version": jaxlib.__version__,
            "python_version": platform.python_version(),
            "device_kind": devs[0].device_kind,
            "device_platform": devs[0].platform,
            "device_count": len(devs)}


def _bench_obs_overhead(batch=512, hidden=512, chunk=25, rounds=36):
    """Price the telemetry layer: steps/sec of an
    instrumented MLP train loop (span + counter + exemplar-carrying
    histogram + request-trace event per step — the optimizer's and the
    serving scheduler's per-step obs work) with recording enabled vs
    kill-switched (``obs.set_enabled``). The acceptance bar is <3%
    overhead — a recording is a clock read plus a lock, ~5 us/step, so
    the workload must be a realistic step (~1 ms), not a toy one whose
    host overhead IS the step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu import obs
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import make_train_step

    model = (nn.Sequential().add(nn.Linear(32, hidden)).add(nn.ReLU())
             .add(nn.Linear(hidden, 10)).add(nn.LogSoftMax()))
    model.build(0, (batch, 32))
    method = SGD(learningrate=0.01)
    step = make_train_step(model, nn.ClassNLLCriterion(), method)
    rng_np = np.random.default_rng(0)
    x = jnp.asarray(rng_np.standard_normal((batch, 32)).astype(np.float32))
    y = jnp.asarray(rng_np.integers(0, 10, batch).astype(np.int32))
    steps_c = obs.counter("bigdl_bench_obs_steps_total",
                          "obs-overhead bench steps")
    lat = obs.histogram("bigdl_bench_obs_step_seconds",
                        "obs-overhead bench step latency")
    tr = obs.mint()  # one request-trace ring priced alongside the rest

    params = jax.tree_util.tree_map(jnp.array, model.params)
    state = model.state
    opt = method.init_state(params)
    # pre-split the keys: a per-step jax.random.split is its own host
    # dispatch, which makes the loop host-bound and charges the obs ops
    # for core contention with the async XLA compute — the real
    # optimizer dispatches ahead and hides host work behind the device,
    # so the bench loop must be device-bound to price honestly
    keys = list(jax.random.split(jax.random.key(0), chunk))
    loss = None
    for i in range(5):  # compile + warmup
        params, state, opt, loss = step(params, state, opt, keys[i], x, y)
    float(loss)

    def timed_chunk(sink):
        # appends per-step wall times to sink: a sub-ms step fits
        # inside a scheduler timeslice, so on a noisy shared host many
        # steps run preemption-free and the low percentiles converge on
        # the true per-step cost (a whole-chunk timing never does — a
        # multi-ms block always absorbs preemptions)
        nonlocal params, state, opt, loss
        for i in range(chunk):
            t1 = time.perf_counter()
            with obs.span("bench/dispatch"):
                params, state, opt, loss = step(params, state, opt,
                                                keys[i], x, y)
            steps_c.inc()
            dt = time.perf_counter() - t1
            lat.observe(dt, exemplar=tr)
            obs.reqtrace.event(tr, "bench_step", i=i)
            sink.append(time.perf_counter() - t1)
        float(loss)

    # the host's throughput drifts on a seconds scale, far more than
    # the telemetry costs, so single pooled on-vs-off comparisons are
    # hopeless.  Instead each round times one on-chunk and one
    # off-chunk back to back (~30 ms apart — no room for drift),
    # alternating the order so neither mode systematically runs first,
    # and the overhead is the MEDIAN of the per-round paired ratios of
    # best step times — a round hit by a preemption is an outlier the
    # median discards
    prev = obs.enabled()
    times = {True: [], False: []}
    per_round = []
    try:
        for r in range(rounds):
            pair = {True: [], False: []}
            for mode in ((True, False) if r % 2 == 0 else (False, True)):
                obs.set_enabled(mode)
                timed_chunk(pair[mode])
            if r >= 2:  # first rounds re-warm
                mid = {m: sorted(ts)[len(ts) // 2]
                       for m, ts in pair.items()}
                per_round.append(mid[False] / mid[True])
                for mode in (True, False):
                    times[mode].extend(pair[mode])
    finally:
        obs.set_enabled(prev)
    per_round.sort()
    q = len(per_round) // 4  # interquartile mean: median-robust, lower var
    mid = per_round[q:len(per_round) - q] or per_round
    overhead = 1.0 - sum(mid) / len(mid)
    on = 1.0 / min(times[True])
    off = 1.0 / min(times[False])
    return {"steps_per_sec_on": round(on, 2),
            "steps_per_sec_off": round(off, 2),
            "overhead_frac": round(max(0.0, overhead), 4)}


def _errors(tree, path=""):
    """Keys of every leg that recorded an error, nested legs included."""
    if not isinstance(tree, dict):
        return []
    if "error" in tree:
        return [path]
    return [k for key, v in tree.items()
            for k in _errors(v, f"{path}.{key}" if path else key)]


def main():
    """Measure and print the JSON line, on a TPU or not at all: a number
    from another backend must never sit under a device metric's name."""
    import jax

    from bigdl_tpu import obs
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(f"bench.py measures the TPU only; JAX found "
                         f"platform {platform!r}")
    name, ips, extra = bench_train_throughput()
    extra["env"] = _env_metadata()
    extra["obs"] = obs.default_registry().snapshot()
    base_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_BASELINE.json")
    baseline = None
    if os.path.exists(base_path):
        with open(base_path) as f:
            baseline = json.load(f).get(name)
    vs = ips / baseline if baseline else 1.0
    print(json.dumps({"metric": f"{name}_images_per_sec_per_chip",
                      "value": round(ips, 2), "unit": "images/sec",
                      "vs_baseline": round(vs, 4), "extra": extra}))
    failed = _errors(extra)
    if failed:
        print(f"bench: failed legs: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
