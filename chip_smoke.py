"""chip_smoke.py: prove on demand that the system starts on the chip.

``python chip_smoke.py`` is one process (a chip belongs to one process at
a time) that drives the main paths through the entry points a user calls,
at the full width of models the repo lists, on whatever TPU JAX reports:

- **serve**: GPT-2 124M (768/12/12, vocab 50257, 1024 positions, random
  weights from a seed) behind ``ServingEngine`` with default arguments,
  mixed-length prompts, greedy and sampled requests; then the same
  requests through the paged engine with its Pallas kernel on, and once
  more with int8 K/V. Checked on logits, not tokens (see ``MARGIN_TOL``).
- **kernels**: each Pallas kernel with ``interpret=False`` against its
  XLA twin at that model's shapes.
- **kv_write**: the decode step's in-place K/V write against the plain
  write at the GPT-2 medium serving cell's table, bit for bit.
- **sampling**: the sampled branch's top-k and nucleus cutoffs by
  threshold (``ops/sampling.py``) against the two sorts, cutoffs bit for
  bit and tokens one for one, at both serving cells' logits tables.
- **decode_attention**: the decode step's length-bounded attention against
  the whole-table read in true float32, at the two serving cells' tables.
- **grouped_product**: the prompt pass's grouped expert product
  (``ops/grouped_matmul.py``) against ``lax.ragged_dot`` at the LFM2 and
  dots3 cells' shapes, parity and the time of each (ISSUE 38's sweep).
- **train**: ResNet-50 NHWC, bf16 compute, batch 256, a few steps through
  ``Optimizer(...).optimize()`` on one repeated seeded batch.
- **four chips** (only when JAX reports four or more): the train leg then
  runs over all devices, and the serve leg at ``tp=4`` and the multichip
  dry run are added, each asserting that what should be sharded really
  spans every chip.

There is no CPU mode, no size flag and no environment switch: without a
TPU it exits non-zero and prints no result. The legs are plain functions
taking sizes so ``tests/test_chip_smoke.py`` can run them tiny on the CPU.

The last line of stdout is the verdict, one JSON object with exactly two
keys: ``{"ok": true, "device": {"platform", "kind", "count"}}``, the device
as JAX reports it. The line before it, ``chip_smoke: report: {...}``, is
the record: ``{"versions", "cache": {"dir", "entries_before",
"entries_added", "mbytes"}, "legs": {name: {"ok", "setup_s" (first pass:
compiles included), "steady_s" (second pass: no compile allowed), ...}}}``.
Any failed leg makes ``ok`` false and the exit code 1; the error is
printed under the leg's name, never dropped.
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
import sys
import tempfile
import time
import traceback
from unittest import mock

import numpy as np

# Why logits and not tokens: with random weights the largest logit changes
# on rounding, so token identity with a reference is not a property the
# system has on this chip. What it must have: every greedy token the
# engine emits is, under the model's uncached full forward with true-f32
# matmuls (``jax.default_matmul_precision("highest")``), within MARGIN_TOL
# of that row's maximum.
#
# The engine's float32 matmuls run at the TPU's default precision (one
# bf16 pass). For GPT-2 124M with N(0, 0.02) weights the logits have a
# standard deviation near 0.55 and a typical top-1/top-2 gap near 0.1.
# Every serve leg reports as ``precision_gap`` the largest difference
# between the default- and the highest-precision uncached forward over the
# rows it checks: 0.0186 on the v5e (my chip run, PR 21). An argmax taken
# under default precision can trail the true maximum by at most twice
# that, which is the tolerance. A defect of the cache path (a wrong row, a
# position off by one, a stale page) moves logits by their own standard
# deviation, more than ten times the tolerance.
MARGIN_TOL = 0.04
# int8 K/V adds a symmetric per-token quantization step of amax/127 (0.4 %
# of the largest element) on every cached key and value, the same order as
# the bf16 rounding above and independent of it (measured deficit 0.0055
# against 0.0 without it, my chip run, PR 21).
MARGIN_TOL_INT8_KV = 0.06

# each wave's prompts share one prefill bucket (16, 128, 1024), so which
# of them the scheduler happens to admit together cannot change what compiles
GPT2_PROMPT_WAVES = ((12,), (70, 100), (600, 700, 960))
GPT2_NEW_TOKENS = 32
SAMPLING = {"top_k": 40, "top_p": 0.9, "temperature": 0.8}


# ------------------------------------------------------------------ serve --
def make_reference(model):
    """The model's uncached full forward as the logit reference: rows
    ``positions`` of one padded sequence, once with true-f32 matmuls and
    once at the backend's default precision (for ``precision_gap``)."""
    import jax

    def rows(params, ids, positions):
        h, _ = model.gpt.apply(params["gpt"], (), ids)
        return model._lm_logits(params, h[0, positions])

    def both(params, ids, positions):
        with jax.default_matmul_precision("highest"):
            exact = rows(params, ids, positions)
        return exact, rows(params, ids, positions)

    fn = jax.jit(both)
    pmax = model.gpt.max_position

    def reference(params, seq, positions):
        ids = np.zeros((1, pmax), np.int32)
        ids[0, :len(seq)] = seq
        exact, default = fn(params, ids, np.asarray(positions, np.int32))
        return np.asarray(exact), np.asarray(default)

    return reference


@contextlib.contextmanager
def _compile_log():
    """``(time, function)`` of every XLA compilation (or load from the
    persistent cache) while the block runs. A steady window must hold
    none: a compile there is a shape, dtype or sharding that changed
    between two calls that should have been the same call."""
    import jax.monitoring
    log = []

    def listen(event, duration, fun_name=None, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            log.append((time.perf_counter(), fun_name))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield log
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def serve_leg(model_kw, params, prompt_waves, n_new, tol, engine_kw=None,
              flags=(), seed=0, wait_s=600.0):
    """Serve seeded prompts through ``ServingEngine`` twice (first pass
    compiles, second must not) and check every emitted token on logits.

    ``prompt_waves``: tuples of prompt lengths, each wave submitted
    together and awaited before the next, so short waves compile their own
    prefill buckets. The last wave also carries one sampled request.
    Returns the leg's record; raises on any violated check."""
    from bigdl_tpu.models.gpt import gpt2_small
    from bigdl_tpu.serving import ServingEngine

    # the paged kernel is a default-off behaviour selected by an
    # environment flag, read at model construction: hold it on for the
    # whole leg, and put the environment back afterwards
    with mock.patch.dict(os.environ, {name: "1" for name in flags}):
        model = gpt2_small(**model_kw)
        reference = make_reference(model)
        rng = np.random.default_rng(seed)
        waves = [[rng.integers(0, model.vocab_size, n).astype(np.int32)
                  for n in wave] for wave in prompt_waves]
        sampled_prompt = rng.integers(0, model.vocab_size,
                                      prompt_waves[0][0]).astype(np.int32)
        engine = ServingEngine(model, params, top_k=SAMPLING["top_k"],
                               top_p=SAMPLING["top_p"], **(engine_kw or {}))
        try:
            def one_pass():
                greedy, sampled = [], None
                for i, wave in enumerate(waves):
                    handles = [engine.submit(p, n_new) for p in wave]
                    if i == len(waves) - 1:
                        sampled = engine.submit(
                            sampled_prompt, n_new,
                            temperature=SAMPLING["temperature"])
                    greedy += [engine.result(h, timeout=wait_s)
                               for h in handles]
                return greedy, engine.result(sampled, timeout=wait_s)

            t0 = time.perf_counter()
            greedy, sampled = one_pass()
            setup_s = time.perf_counter() - t0
            traces = {k: engine.stats[k]
                      for k in ("prefill_traces", "step_traces")}
            t0 = time.perf_counter()
            with _compile_log() as compiled:
                greedy2, _ = one_pass()
            steady_s = time.perf_counter() - t0
            metrics = engine.metrics()
            placement = _placement(engine, model)
            kv_write = engine.slots.kv_write
            attn_read = engine.slots.attn_read
            sampler = engine.slots.sampler
        finally:
            engine.shutdown()

    n_req = sum(len(w) for w in waves) + 1
    prompts = [p for wave in waves for p in wave]
    for p, out in zip(prompts + [sampled_prompt], greedy + [sampled]):
        _require(len(out) == len(p) + n_new and (out[:len(p)] == p).all(),
                 f"request of {len(p)} tokens came back with {len(out)}")
    _require(metrics["admitted"] == metrics["retired"] == 2 * n_req,
             f"admitted {metrics['admitted']} retired {metrics['retired']} "
             f"of {2 * n_req} submitted")
    _require(metrics["failures"] == 0, f"{metrics['failures']} failures")
    # documented budget (docs/serving.md): the step executable compiles
    # once, twice when a paged engine also builds its copy-on-write step;
    # prefill compiles once per bucket (dense) or once (chunked)
    _require(traces["step_traces"] <= 2, f"step_traces {traces}")
    _require(traces["prefill_traces"] <= len(prompt_waves) + 1,
             f"prefill_traces {traces}")
    _require(not compiled, f"the steady pass compiled "
                           f"{[name for _, name in compiled]}")
    # a donated buffer read after donation, or state leaking from one
    # request to the next, shows as a second pass that differs
    same = sum(bool((a == b).all()) for a, b in zip(greedy, greedy2))

    deficit, gap = 0.0, 0.0
    for p, out in zip(prompts, greedy):
        pos = np.arange(len(p) - 1, len(out) - 1)
        exact, default = reference(params, out, pos)
        _require(np.isfinite(exact).all(), "reference logits not finite")
        chosen = exact[np.arange(n_new), out[len(p):]]
        deficit = max(deficit, float((exact.max(-1) - chosen).max()))
        gap = max(gap, float(np.abs(exact - default).max()))
    _require(deficit <= tol,
             f"a greedy token trails the reference maximum by {deficit:.4f} "
             f"> {tol} (precision gap {gap:.4f})")
    # the sampled request: every token must come from the reference's
    # top-k set, up to the same rounding at its boundary
    p = sampled_prompt
    exact, _ = reference(params, sampled, np.arange(len(p) - 1,
                                                    len(sampled) - 1))
    kth = np.sort(exact, axis=-1)[:, -SAMPLING["top_k"]]
    outside = float((kth - exact[np.arange(n_new), sampled[len(p):]]).max())
    _require(outside <= tol,
             f"a sampled token lies {outside:.4f} below the top-"
             f"{SAMPLING['top_k']} boundary")
    return {"ok": True, "requests": 2 * n_req, "setup_s": round(setup_s, 2),
            "steady_s": round(steady_s, 2), **traces,
            "margin_deficit": round(deficit, 5),
            "precision_gap": round(gap, 5), "tolerance": tol,
            "second_pass_identical": f"{same}/{len(greedy)}",
            "tp_degree": metrics["tp_degree"], "kv_write": kv_write,
            "attn_read": attn_read, "sampler": sampler,
            **placement}


def _placement(engine, model):
    """Under a tp mesh: parameters and the K/V tables must sit on every
    device of the mesh, and the leaves whose spec names a mesh axis but
    which ``ModelLayout.fit`` replicated because the axis does not divide
    them are listed (reported, not fixed: GPT-2's vocabulary of 50257 is
    odd, so the embedding and the tied LM head are such leaves)."""
    import jax
    layout = engine.layout
    if layout is None:
        return {}
    n = layout.num_devices
    slots = engine.slots
    _spans(slots.params, n, "parameters")
    _spans(slots._pools if engine.paged else slots._cache, n, "K/V tables")
    specs = jax.tree_util.tree_leaves(
        layout.param_specs(model, slots.params),
        is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    leaves = jax.tree_util.tree_leaves_with_path(slots.params)
    return {"mesh_devices": n, "replicated_despite_spec": [
        jax.tree_util.keystr(path) for (path, leaf), spec
        in zip(leaves, specs)
        if any(spec) and leaf.sharding.is_fully_replicated]}


def _spans(tree, n, what):
    import jax
    for leaf in jax.tree_util.tree_leaves(tree):
        _require(len(leaf.sharding.device_set) == n,
                 f"{what}: a {leaf.shape} leaf sits on "
                 f"{len(leaf.sharding.device_set)} of {n} devices")


def _require(cond, message):
    if not cond:
        raise AssertionError(message)


# ---------------------------------------------------------------- kernels --
def _rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-9))


def kernels_leg(heads=12, head_dim=64, seq=1024, long_shape=(1, 8, 8192, 64),
                tail=512, slots=8, page_size=16, chunk=64):
    """Each Pallas kernel, compiled (``interpret=False`` passed, so it can
    never silently interpret), against its XLA twin under true-f32
    matmuls. Tolerances are relative to the twin's largest element:
    ``2e-2`` where a bf16 pass or bf16 operands are in the path (2^-8
    rounding on O(1) values, summed over a softmax that averages it
    down)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.flash_attention import flash_attention
    from bigdl_tpu.ops.paged_attention import paged_pool_attention
    from bigdl_tpu.parallel.sequence import (
        full_attention, paged_attention, paged_gather, paged_gather_dequant,
        paged_write, paged_write_quant)

    tol = 2e-2
    out = {}
    t_start = time.perf_counter()

    def exact(fn):
        def run(*a):
            with jax.default_matmul_precision("highest"):
                return fn(*a)
        return jax.jit(run)

    def check_flash(name, flash_loss, twin_loss, q, k, v):
        """Output and dq/dk/dv of the kernel against its twin; both
        callables return ``(scalar loss, output)``."""
        grad = functools.partial(jax.value_and_grad, argnums=(0, 1, 2),
                                 has_aux=True)
        (_, o), g = jax.jit(grad(flash_loss))(q, k, v)
        (_, o_ref), g_ref = exact(grad(twin_loss))(q, k, v)
        errs = [_rel_err(a, b) for a, b in zip((o, *g), (o_ref, *g_ref))]
        out[name] = round(max(errs), 5)
        _require(max(errs) <= tol, f"{name}: fwd/dq/dk/dv rel err {errs}")

    def normal(seed, shape, dtype):
        return [jax.random.normal(kk, shape, jnp.float32).astype(dtype)
                for kk in jax.random.split(jax.random.key(seed), 4)]

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=False).astype(jnp.float32)

    # flash attention, forward and backward, whole sequence against
    # full_attention
    for dtype in (jnp.float32, jnp.bfloat16):
        q, k, v, w = normal(1, (1, heads, seq, head_dim), dtype)
        w = w.astype(jnp.float32)

        def flash_loss(q, k, v):
            o = flash(q, k, v)
            return jnp.sum(o * w), o

        def twin_loss(q, k, v):
            o = full_attention(*(t.astype(jnp.float32) for t in (q, k, v)),
                               causal=True)
            return jnp.sum(o * w), o

        check_flash(f"flash_s{seq}_{jnp.dtype(dtype).name}", flash_loss,
                    twin_loss, q, k, v)

    # flash attention at the long shape: the last ``tail`` query rows
    # against the whole context (the (S, S) twin would not fit beside it)
    s, d = long_shape[2:]
    q, k, v, w = normal(2, long_shape, jnp.bfloat16)
    wt = w[:, :, -tail:].astype(jnp.float32)

    def flash_tail(q, k, v):
        o = flash(q, k, v)[:, :, -tail:]
        return jnp.sum(o * wt), o

    def twin_tail(q, k, v):
        q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, -tail:], k) * d ** -0.5
        rows = s - tail + jnp.arange(tail)[:, None]
        scores = jnp.where(jnp.arange(s)[None, :] <= rows, scores, -jnp.inf)
        o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)
        return jnp.sum(o * wt), o

    check_flash(f"flash_s{s}_bfloat16_tail{tail}", flash_tail, twin_tail,
                q, k, v)

    # paged attention: pools as the allocator leaves them (page runs in
    # position order, sentinel tails, one empty slot), decode and chunk
    lengths = [5, 17, seq // 3, 1, seq - chunk, seq, 0, chunk][:slots]
    per_row = seq // page_size
    n_pages = sum(-(-max(n, 1) // page_size) for n in lengths) + 1
    table = np.full((slots, per_row), n_pages, np.int32)
    pages = np.full((slots, seq), n_pages, np.int32)
    nxt = 0
    for i, n in enumerate(lengths):
        for j in range(-(-n // page_size)):
            table[i, j] = nxt
            nxt += 1
        pages[i, :n] = table[i, np.arange(n) // page_size]
    offs = np.broadcast_to(np.arange(seq) % page_size, (slots, seq))
    kv = jax.random.normal(jax.random.key(3),
                           (2, slots, heads, seq, head_dim), jnp.float32)
    shape = (n_pages, heads, page_size, head_dim)
    for quant in (False, True):
        if quant:
            zeros = jnp.zeros(shape[:3], jnp.float32)
            pk, ks = paged_write_quant(jnp.zeros(shape, jnp.int8), zeros,
                                       kv[0], pages, offs)
            pv, vs = paged_write_quant(jnp.zeros(shape, jnp.int8), zeros,
                                       kv[1], pages, offs)
            pool = {"k": pk, "v": pv, "k_scale": ks, "v_scale": vs}
        else:
            pool = {"k": paged_write(jnp.zeros(shape), kv[0], pages, offs),
                    "v": paged_write(jnp.zeros(shape), kv[1], pages, offs)}

        def twin(q, pool, table, q_pos):
            if quant:
                kf = paged_gather_dequant(pool["k"], pool["k_scale"], table,
                                          jnp.float32)
                vf = paged_gather_dequant(pool["v"], pool["v_scale"], table,
                                          jnp.float32)
            else:
                kf = paged_gather(pool["k"], table)
                vf = paged_gather(pool["v"], table)
            return paged_attention(q, kf, vf, q_pos)

        for c in (1, chunk):
            q = jax.random.normal(jax.random.key(4 + c),
                                  (slots, heads, c, head_dim), jnp.float32)
            start = np.maximum(np.asarray(lengths) - c, 0)
            q_pos = jnp.asarray(start[:, None] + np.arange(c), jnp.int32)
            got = jax.jit(lambda *a: paged_pool_attention(
                *a, interpret=False))(q, pool, table, q_pos)
            want = exact(twin)(q, pool, jnp.asarray(table), q_pos)
            name = f"paged_{'int8' if quant else 'f32'}_c{c}"
            # queries past a row's write frontier (and the empty slot) are
            # junk on both paths; serving discards them too
            written = (np.asarray(q_pos) < np.asarray(lengths)[:, None])
            written = written[:, None, :, None]
            err = _rel_err(np.where(written, got, 0.0),
                           np.where(written, want, 0.0))
            out[name] = round(err, 5)
            _require(np.isfinite(np.asarray(got)).all(),
                     f"{name}: not finite")
            _require(err <= tol, f"{name}: rel err {err}")

    return {"ok": True, "setup_s": round(time.perf_counter() - t_start, 2),
            "tolerance": tol, "errors": out}


def kv_write_leg(slots=48, heads=16, seq=1024, head_dim=64,
                 interpret=False):
    """``ops/kv_write.py`` against the plain write it replaces in the
    serving step at the GPT-2 medium cell's table, ``f32[48,16,1024,64]``
    and its bfloat16 twin, every slot at another position: the two tables
    must come back the same bit for bit, so a Mosaic that accepts the
    kernel and writes the wrong lane is caught outside the benchmark;
    and again with 6 of the slots live, where the others must come back
    as they went in.
    Also that the table as allocated selects the kernel here
    (``in_place_applies``: a TPU, positions minor on the device)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from bigdl_tpu.ops.kv_write import (in_place_applies, kv_write,
                                        plain_write)

    def differing(a, b):
        whole = {2: jnp.uint16, 4: jnp.uint32}[a.dtype.itemsize]
        return jnp.sum(lax.bitcast_convert_type(a, whole)
                       != lax.bitcast_convert_type(b, whole))

    t_start = time.perf_counter()
    pos = np.random.default_rng(0).integers(0, seq, slots)
    # both ends of the table and both sides of a tile's edge among them
    edges = [0, seq - 1, seq // 2 - 1, seq // 2, 7, 8][:slots]
    pos[:len(edges)] = edges
    pos = jnp.asarray(pos, jnp.int32)
    few = np.zeros(slots, bool)
    few[np.random.default_rng(1).permutation(slots)[:6]] = True
    kernel = jax.jit(functools.partial(kv_write, interpret=interpret),
                     donate_argnums=(0, 1))
    selected = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        name = jnp.dtype(dtype).name
        draw = jax.jit(lambda k, shape: jax.random.normal(
            k, shape, jnp.float32).astype(dtype), static_argnums=1)
        keys = jax.random.split(jax.random.key(7), 4)
        table = (slots, heads, seq, head_dim)
        new = (slots, heads, 1, head_dim)
        k_table, v_table = draw(keys[0], table), draw(keys[1], table)
        rest = (draw(keys[2], new), draw(keys[3], new), pos)
        selected[name] = in_place_applies(k_table)
        _require(selected[name] or interpret,
                 f"kv_write {name}: the table as allocated does not select "
                 f"the kernel (layout {k_table.format.layout})")
        want = jax.jit(plain_write)(k_table, v_table, *rest)
        # a live slot the plain write's, a free one the table's own
        held = jnp.asarray(few)[:, None, None, None]
        masked = [jnp.where(held, w, t)
                  for w, t in zip(want, (k_table, v_table))]
        got = kernel(jnp.array(k_table), jnp.array(v_table), *rest,
                     jnp.asarray(few))
        wrong = int(differing(got[0], masked[0])
                    + differing(got[1], masked[1]))
        _require(wrong == 0, f"kv_write {name}: {wrong} elements differ "
                             f"with 6 of {slots} slots live")
        del masked
        got = kernel(k_table, v_table, *rest)        # consumes the tables
        wrong = int(differing(got[0], want[0]) + differing(got[1], want[1]))
        _require(wrong == 0, f"kv_write {name}: {wrong} elements differ "
                             f"from the plain write")
    return {"ok": True, "setup_s": round(time.perf_counter() - t_start, 2),
            "differing_elements": 0, "selected": selected}


def sampling_leg(tables=((48, 50257), (96, 65536)), top_k=40, top_p=0.9,
                 keys=4, interpret=False):
    """``ops/sampling.py`` against the two sorts it replaces in the
    serving step's sampled branch, at the GPT-2 medium cell's logits
    table and at LFM2's (the sorts take 17-19 s a table to compile, most
    of this leg): the cutoff a row must be the sorts' own bit for bit, and ``select_tokens`` must draw the same
    tokens on the same key whichever sampler it is given, rows of
    temperature 0 among rows at 0.6 to 1.3. Also that the table as
    allocated selects the kernel here (``applies``)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops import sampling
    from bigdl_tpu.serving.slots import select_tokens

    def sorted_cutoff(scaled):
        kth = jax.lax.top_k(scaled, top_k)[0][..., -1:]
        ordered = jnp.sort(jnp.where(scaled < kth, -jnp.inf, scaled),
                           axis=-1)[..., ::-1]
        probs = jax.nn.softmax(ordered, axis=-1)
        keep = jnp.sum((jnp.cumsum(probs, axis=-1) - probs < top_p)
                       .astype(jnp.int32), axis=-1, keepdims=True)
        return jnp.take_along_axis(ordered, keep - 1, axis=-1)

    t_start = time.perf_counter()
    pick = jax.jit(select_tokens, static_argnums=(3, 4, 5))
    selected, differ = {}, {}
    for slots, vocab in tables:
        name = f"{slots}x{vocab}"
        logits = 3.0 * jax.random.normal(jax.random.key(5), (slots, vocab))
        temps = jnp.where(jnp.arange(slots) % 3 == 0, 0.0,
                          jnp.linspace(0.6, 1.3, slots))
        selected[name] = bool(sampling.applies(logits))
        _require(selected[name] or interpret,
                 f"sampling {name}: the logits table as allocated does not "
                 f"select the kernel")
        scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
        got = jax.jit(lambda x: sampling.cutoffs(
            x, top_k, top_p, interpret=interpret))(scaled)
        cuts = int((got != jax.jit(sorted_cutoff)(scaled)).sum())
        _require(cuts == 0, f"sampling {name}: {cuts} of {slots} cutoffs "
                            f"differ from the sorts'")
        diff = 0
        for i in range(keys):
            key = jax.random.key(100 + i)
            want, _ = pick(logits, temps, key, top_k, top_p, "sort")
            got, _ = pick(logits, temps, key, top_k, top_p, "kernel")
            diff += int((np.asarray(got) != np.asarray(want)).sum())
        differ[name] = diff
        _require(diff == 0, f"sampling {name}: {diff} of {keys * slots} "
                            f"tokens differ from the sorts'")
    return {"ok": True, "setup_s": round(time.perf_counter() - t_start, 2),
            "differing_tokens": differ, "selected": selected}


def decode_attention_leg(tables=(((48, 16, 1024, 64), 1, "float32"),
                                 ((96, 8, 2048, 64), 4, "bfloat16")),
                         tol=2e-5, interpret=False):
    """``ops/decode_attention.py`` against the read it replaces, the
    softmax over every position under a length mask with true-float32
    products, at the GPT-2 medium cell's table and at LFM2's (bfloat16,
    four queries a K/V head): slots at both sides of a block's edge, at
    the table's end, and free (count 0: zeros). A Mosaic that accepts the
    kernel and reads a wrong block, or past a length, is caught outside
    the benchmark. Also that the table as allocated selects the kernel
    here (``applies``)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.decode_attention import applies, decode_attention

    def whole_table(q, k, v, counts):
        k, v = k.astype(jnp.float32), v.astype(jnp.float32)
        s = jnp.einsum("bgrd,bgkd->bgrk", q, k,
                       precision="highest") * q.shape[-1] ** -0.5
        seen = (jnp.arange(k.shape[2])[None, :]
                < counts[:, None])[:, None, None, :]
        p = jnp.where(seen, jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1),
                      0.0)          # a free slot's row is 0/0
        return jnp.einsum("bgrk,bgkd->bgrd", p, v, precision="highest")

    t_start = time.perf_counter()
    selected, errors = {}, {}
    for shape, reps, dtype in tables:
        slots, heads, seq, head_dim = shape
        counts = np.random.default_rng(0).integers(1, seq + 1, slots)
        edges = [1, 127, 128, 129, seq, 0, 0, seq - 1][:slots]
        counts[:len(edges)] = edges
        counts = jnp.asarray(counts, jnp.int32)
        draw = jax.jit(lambda k, shape: jax.random.normal(
            k, shape, jnp.float32).astype(dtype), static_argnums=1)
        keys = jax.random.split(jax.random.key(11), 3)
        q = jax.random.normal(keys[0], (slots, heads, reps, head_dim))
        k_table, v_table = draw(keys[1], shape), draw(keys[2], shape)
        selected[dtype] = applies(k_table)
        _require(selected[dtype] or interpret,
                 f"decode_attention {dtype}: the table as allocated does "
                 f"not select the kernel (layout {k_table.format.layout})")
        got = jax.jit(functools.partial(decode_attention,
                                        interpret=interpret))(
            q, k_table, v_table, counts)
        want = jax.jit(whole_table)(q, k_table, v_table, counts)
        errors[dtype] = float(jnp.max(jnp.abs(got - want)))
        _require(errors[dtype] <= tol,
                 f"decode_attention {dtype}: {errors[dtype]} from the "
                 f"whole-table read, over {tol}")
    return {"ok": True, "setup_s": round(time.perf_counter() - t_start, 2),
            "tolerance": tol, "errors": errors, "selected": selected}


# the grouped expert products of ISSUE 38, (name, assignments, contraction,
# output, experts routed over, experts held, experts a token, share of the
# tokens live): LFM2's step (96 slots x 4) and prompt passes (4 rows x
# buckets 32-512 x 4, a fifth of the positions real as the cell's
# ``prefill_useful_share.batch`` reads, and once every one), dots3's step
# (36 x 8) and a block of its prompt pass (2048 x 8), 32 of 256 held
GROUPED_PRODUCT_SHAPES = tuple(
    (f"{name}.{mat}", rows, *(dims if mat == "w13" else dims[::-1]), *rest)
    for name, rows, dims, rest in (
        ("lfm2.step", 384, (2048, 1536), (64, 64, 4, 1.0)),
        ("lfm2.prefill512", 512, (2048, 1536), (64, 64, 4, 0.2)),
        ("lfm2.prefill1024", 1024, (2048, 1536), (64, 64, 4, 0.2)),
        ("lfm2.prefill2048", 2048, (2048, 1536), (64, 64, 4, 0.2)),
        ("lfm2.prefill4096", 4096, (2048, 1536), (64, 64, 4, 0.2)),
        ("lfm2.prefill8192", 8192, (2048, 1536), (64, 64, 4, 0.2)),
        ("lfm2.prefill8192full", 8192, (2048, 1536), (64, 64, 4, 1.0)),
        ("dots3.step", 288, (5120, 1536), (256, 32, 8, 1.0)),
        ("dots3.prefill", 16384, (5120, 1536), (256, 32, 8, 1.0)))
    for mat in ("w13", "w2"))


def grouped_product_leg(shapes=GROUPED_PRODUCT_SHAPES, tilings=None,
                        reps=10, tol=1e-3, interpret=False):
    """The prompt pass's grouped expert product (``ops/grouped_matmul.py``)
    against ``lax.ragged_dot``, which it replaces there, at the shapes the
    cells make: the rows of every group must agree (bfloat16 operands,
    float32 sums; ``tol`` relative to the largest), the rows after the
    groups (padding and experts held elsewhere) must come out zero. The
    routing is drawn as the layer's: each token picks distinct experts,
    a held one's rows go to its group, the rest trail. Both are timed on
    the host's clock, ``reps`` calls back to back, the best of three
    rounds: ms a call and the weights of the experts hit over it in GB/s.
    ``tilings`` (a list of ``(tm, tk, tn)``, each fitted to the shape as
    the product fits its own) is the sweep; None times the product's
    own tiling only. Also which product the layer's rule
    (``nn.moe.grouped_product``) gives each shape."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.nn.moe import grouped_product
    from bigdl_tpu.ops.grouped_matmul import grouped_matmul, tiling
    from bigdl_tpu.ops.pallas_util import fit_block

    def timed(fn, *args):
        fn(*args).block_until_ready()
        best = float("inf")
        for _ in range(3 if reps else 0):
            t = time.perf_counter()
            for _ in range(reps):
                out = fn(*args)
            out.block_until_ready()
            best = min(best, (time.perf_counter() - t) / reps * 1e3)
        return round(best, 4) if reps else None

    t_start = time.perf_counter()
    rng = np.random.default_rng(38)
    cases = {}
    for name, rows, k, n, experts, held, per_token, live in shapes:
        tokens = rows // per_token
        chosen = np.argsort(rng.random((tokens, experts)), 1)[:, :per_token]
        chosen[int(round(live * tokens)):] = held      # dead: trailing
        sizes = np.bincount(chosen.ravel(), minlength=held + 1)[:held]
        keys = jax.random.split(jax.random.key(rows + k), 2)
        lhs = jax.random.normal(keys[0], (rows, k), jnp.bfloat16)
        rhs = (0.02 * jax.random.normal(keys[1], (held, k, n))).astype(
            jnp.bfloat16)
        sizes = jnp.asarray(sizes, jnp.int32)
        held_rows = int(sizes.sum())
        want = jax.jit(lambda a, b, s: jax.lax.ragged_dot(
            a, b, s, preferred_element_type=jnp.float32))
        ref = want(lhs, rhs, sizes)
        own = tiling(rows, k, n)
        sweep = {own, *((min(rows, tm), fit_block(k, tk), fit_block(n, tn))
                        for tm, tk, tn in tilings or ())}
        hit = int((sizes > 0).sum())
        gbs = lambda ms: round(hit * k * n * 2 / ms / 1e6, 1) if ms else None
        rec = {"rows": rows, "held_rows": held_rows, "experts_hit": hit,
               "rule": grouped_product(rows)}
        rec["ragged_dot_ms"] = timed(want, lhs, rhs, sizes)
        rec["ragged_dot_gb_s"] = gbs(rec["ragged_dot_ms"])
        rec["gmm_ms"] = {}
        for tiles in sorted(sweep):
            fn = jax.jit(functools.partial(grouped_matmul, tiles=tiles,
                                           interpret=interpret))
            got = fn(lhs, rhs, sizes)
            err = _rel_err(got[:held_rows], ref[:held_rows])
            trail = float(jnp.abs(got[held_rows:]).max(initial=0.0))
            _require(err <= tol and trail == 0.0,
                     f"grouped_product {name} {tiles}: {err} from "
                     f"ragged_dot (over {tol}), trailing rows up to {trail}")
            rec["gmm_ms"]["x".join(map(str, tiles))] = timed(
                fn, lhs, rhs, sizes)
        own_ms = rec["gmm_ms"]["x".join(map(str, own))]
        rec.update(own_tiling=list(own), gmm_own_ms=own_ms,
                   gmm_own_gb_s=gbs(own_ms))
        cases[name] = rec
        print(f"chip_smoke: grouped_product {name}: {json.dumps(rec)}",
              flush=True)
        del lhs, rhs, ref
    return {"ok": True, "setup_s": round(time.perf_counter() - t_start, 2),
            "tolerance": tol, "cases": cases}


# how far the logits of the two-layer latent model, served in bfloat16,
# may lie from the float32 reference's: the root of their mean square, and
# the widest single one. A bfloat16 rounding turns a selection at its
# 2048th place, as it turns an expert choice, and a turned selection moves
# a few logits by a good part of their spread (1.44): the widest read 1.28
# on the chip (my chip run, PR 34), so it is held loosely; a wrong row, a
# wrong selection or a slipped ring moves EVERY logit of a position by
# that spread, which is what the mean square holds
LATENT_TOL = {"rms": 0.25, "widest": 3.0}


def latent_read_leg(model_kw=None, first=4096, steps=64, dtype="bfloat16",
                    tol=LATENT_TOL, weights_spec=None):
    """One full layer (latent attention over the positions an indexer
    picks) and one window layer of ``models/dots3.py`` at the published
    widths, a dense feed-forward each: two streams are prefilled just
    past position ``first`` and decoded for ``steps`` steps through the
    slot table, and after the prefill and after every step each stream's
    logits are held to ``benchmarks/reference/dots3.py``'s rows of the
    whole sequence (true float32, K and V expanded, a plain top-k). The
    contexts are twice the selection and eight times the window, so a
    step that reads a wrong row, selects wrongly or lets the ring slip is
    caught here, without a benchmark run: the check a later kernel for
    the selected read can use. ``tol`` holds the root mean square and the
    widest of the logit differences; both readings are reported."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import weights
    from benchmarks.reference import dots3 as reference_mod
    from bigdl_tpu.models.dots3 import Dots3ForCausalLM
    from bigdl_tpu.serving.slots import SlotManager

    kw = dict(vocab_size=2048, layer_types=["full_attention",
                                            "sliding_attention"],
              first_k_dense_replace=2, max_position=first + 2048,
              rope_theta=8e7, swa_rope_theta=5e4)
    kw.update(model_kw or {})
    t_start = time.perf_counter()
    model = Dots3ForCausalLM(**kw)
    # the reference reads every size from the keyword arguments
    import inspect
    defaults = {k: v.default for k, v in inspect.signature(
        Dots3ForCausalLM.__init__).parameters.items()
        if v.default is not inspect.Parameter.empty}
    ref_kw = dict(defaults, **kw)
    shapes = jax.eval_shape(lambda k: model.setup(k, None)[0],
                            jax.random.key(0))
    params = weights.make_params(
        shapes, 34, weights_spec or {"std": 0.02, "gain_std": 0.1,
                                     "bias_std": 0.1}, dtype=dtype)
    reference, _ = reference_mod.make({"constructor_kwargs": ref_kw})
    pmax = kw["max_position"]
    rng = np.random.default_rng(34)
    lengths = (first + 1, first + 37)
    seqs = [rng.integers(0, kw["vocab_size"], pmax).astype(np.int32)
            for _ in lengths]
    sm = SlotManager(model, params, max_slots=2, window=1)
    slots = [sm.admit([s[:n]])[0] for s, n in zip(seqs, lengths)]
    want = [np.asarray(reference(
        params, s, np.arange(n - 1, n + steps, dtype=np.int32)))
        for s, n in zip(seqs, lengths)]
    worst, squares, spread = 0.0, [], float(np.std(want[0]))
    with _compile_log() as compiles:
        for step in range(steps + 1):
            if step == 2:
                steady_from = time.perf_counter()
            got = np.asarray(sm._logits, np.float32)
            for slot, w in zip(slots, want):
                gap = np.abs(got[slot] - w[step])
                worst = max(worst, float(gap.max()))
                squares.append(float(np.square(gap, dtype=np.float64).mean()))
            if step == steps:
                break
            # feed the sequence's own next token: plant it as the only
            # finite logit of the slot's row
            forced = np.full(got.shape, -np.inf, np.float32)
            for slot, s, n in zip(slots, seqs, lengths):
                forced[slot, s[n + step]] = 0.0
            sm._logits = jnp.asarray(forced, sm._logits.dtype)
            sm.step()
    late = [name for t, name in compiles if steps >= 2 and t >= steady_from]
    _require(not late, f"latent_read: compiled inside the steady steps: "
                       f"{late}")
    rms = float(np.sqrt(np.mean(squares)))
    _require(rms <= tol["rms"] and worst <= tol["widest"],
             f"latent_read: the logits lie {rms} (root mean square; widest "
             f"{worst}) from the reference's, over {tol} (spread {spread})")
    return {"ok": True, "setup_s": round(time.perf_counter() - t_start, 2),
            "tolerance": tol, "logit_gap": worst, "logit_gap_rms": rms,
            "logit_spread": spread,
            "positions": [first, first + 37 + steps],
            "selected_of": [kw.get("index_topk", 2048), first],
            "kv_write": sm.kv_write, "attn_read": sm.attn_read}


# the EvaByte cell's tables, a layer: 20 slots, a window of 2048 rows and
# 1024 summaries (16384 positions in chunks of 16), 32 heads of 128; the
# cell's model has 8 layers
EVA_TABLES = (20, 2048, 1024, 32, 128)
EVA_CHUNK, EVA_LAYERS = 16, 8
# what float32 sums in another order may add to the kernel's read, beside
# the rounding of the softmax's weights to the tables' dtype (which the
# masked read rounds too): the leg holds each output to ``u x sum_r p_r
# |v_r|`` from the true read, ``u`` the dtype's unit roundoff, plus this.
# Two roundings of the same weights differ by up to 2 u of a weight, so
# the kernel and the masked read may lie 2 u x that mass apart (0.0061
# in the mix of edge positions on a v5e, where few rows share the
# weights); a wrong row or block moves an output by a whole weight's
# share, many times the bound
EVA_TOL = 1e-5


def eva_read_leg(tables=EVA_TABLES, chunk=EVA_CHUNK, layers=EVA_LAYERS,
                 dtype="bfloat16", tol=EVA_TOL, reps=10, interpret=False):
    """``ops/eva_attention.py`` against the read it replaces
    (``EvaAttention._attend``, every row of both tables scored under the
    decode step's masks) and the true float32 read, at the EvaByte cell's
    tables, ``layers`` layers' worth of them, at several mixes of
    positions: the cell's (a window
    or more deep), each window's first and last row, a chunk's closing
    position and the table's end, the first window (no summary seen),
    every slot at the deepest position, and a third of the slots free
    (their rows NaN: a free slot must read nothing and give zeros). Each
    output must lie within the rounding of the softmax's weights to the
    tables' dtype (and ``tol``) of the true read's (``EVA_TOL``); the
    masked read's share of that bound is reported beside, and the widest
    difference between the two reads as ``error``. Both
    reads are timed on the host's clock, all layers' calls back to back,
    ``reps`` times, the best of three rounds: ms a layer, and the kernel's
    bytes fetched over its time in GB/s. Also that the tables as
    allocated select the kernel here (``applies``)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models.evabyte import EvaAttention
    from bigdl_tpu.ops import eva_attention as kernel

    slots, window, far_rows, heads, head_dim = tables
    pmax = far_rows * chunk
    att = EvaAttention(heads * head_dim, heads, window, chunk)
    t_start = time.perf_counter()
    draw = jax.jit(lambda k, shape: jax.random.normal(
        k, shape, jnp.float32).astype(dtype), static_argnums=1)
    keys = iter(jax.random.split(jax.random.key(45), 5 * layers))
    near = (slots, window, heads, head_dim)
    far = (slots, far_rows, heads, head_dim)
    qs = [jax.random.normal(next(keys), (slots, heads, head_dim))
          for _ in range(layers)]
    stack = [[draw(next(keys), shape) for shape in (near, near, far, far)]
             for _ in range(layers)]
    selected = all(kernel.applies(t) for t in stack[0])
    _require(selected or interpret,
             f"eva_read: the tables as allocated do not select the kernel "
             f"({dtype}, {list(near)} and {list(far)})")

    def twin(qs, stack, pos, live):
        local = jnp.arange(window)[None, :] <= (pos % window)[:, None]
        seen = jnp.arange(far_rows)[None, :] \
            < ((pos // window) * (window // chunk))[:, None]
        return jnp.stack([jnp.where(live[:, None, None], att._attend(
            q, *tabs, local[:, None, :], seen[:, None, :]), 0.0)
            for q, tabs in zip(qs, stack)])

    def own(qs, stack, pos, live):
        return jnp.stack([kernel.eva_attention(
            q, *tabs, pos, live, window=window, chunk=chunk,
            interpret=interpret) for q, tabs in zip(qs, stack)])

    def true_read(q, tabs, pos, live):
        """One layer read in true float32, the weights unrounded: the
        outputs, and ``sum_r p_r |v_r|`` (what rounding the weights to
        the tables' dtype may move them by, in units of its roundoff)."""
        f32, hi = jnp.float32, jax.lax.Precision.HIGHEST
        wk, wv, sk, sv = tabs
        q = q.astype(wk.dtype)
        local = jnp.arange(window)[None, :] <= (pos % window)[:, None]
        seen = jnp.arange(far_rows)[None, :] \
            < ((pos // window) * (window // chunk))[:, None]
        s = jnp.concatenate([
            jnp.where(local[:, None], jnp.einsum(
                "bhd,bkhd->bhk", q, wk, precision=hi,
                preferred_element_type=f32), -jnp.inf),
            jnp.where(seen[:, None], jnp.einsum(
                "bhd,bchd->bhc", q, sk, precision=hi,
                preferred_element_type=f32), -jnp.inf)], -1)
        p = jax.nn.softmax(s * head_dim ** -0.5, -1)

        def mix(v_near, v_far):
            return (jnp.einsum("bhk,bkhd->bhd", p[..., :window],
                               v_near.astype(f32), precision=hi)
                    + jnp.einsum("bhc,bchd->bhd", p[..., window:],
                                 v_far.astype(f32), precision=hi))

        keep = live[:, None, None]
        return (jnp.where(keep, mix(wv, sv), 0.0),
                jnp.where(keep, mix(jnp.abs(wv), jnp.abs(sv)), 0.0))

    def timed(fn, *args):
        fn(*args).block_until_ready()
        best = float("inf")
        for _ in range(3 if reps else 0):
            t = time.perf_counter()
            for _ in range(reps):
                out = fn(*args)
            out.block_until_ready()
            best = min(best, (time.perf_counter() - t) / reps / layers * 1e3)
        return round(best, 4) if reps else None

    rng = np.random.default_rng(45)
    edges = [0, window - 1, window, window + 1, 2 * window - 1, pmax - 1,
             chunk - 1, window + chunk - 1, 3 * window - 1, 3 * window]
    mixes = {
        "cell": rng.integers(window // 2, pmax - window, slots),
        "edges": np.resize(edges, slots),
        "first_window": rng.integers(0, window, slots),
        "deepest": np.full(slots, pmax - 1),
        "a_third_free": rng.integers(window // 2, pmax - window, slots)}
    twin_fn, own_fn = jax.jit(twin), jax.jit(own)
    true_fn = jax.jit(true_read)
    # the unit roundoff of the weights rounded to the tables' dtype
    unit = float(jnp.finfo(dtype).eps) / 2
    cases = {}
    for name, pos in mixes.items():
        live = np.ones(slots, bool)
        if name == "a_third_free":
            live[::3] = False
        pos, live_d = jnp.asarray(pos, jnp.int32), jnp.asarray(live)
        if not live.all():
            # what a free slot's rows hold must not show (the tables are
            # replaced, not copied: two sets would not fit the chip)
            free = jnp.asarray(~live)[:, None, None, None]
            for ls in stack:
                for i, t in enumerate(ls):
                    ls[i] = jnp.where(free, jnp.nan, t).astype(t.dtype)
                del t
        got = np.asarray(own_fn(qs, stack, pos, live_d))
        want = np.asarray(twin_fn(qs, stack, pos, live_d))
        # each read's widest distance from the true one, as a share of
        # what the rounding of the weights allows (1 is the bound)
        share = {"kernel": 0.0, "masked": 0.0}
        for layer, (q, tabs) in enumerate(zip(qs, stack)):
            truth, mass = map(np.asarray, true_fn(q, tabs, pos, live_d))
            allowed = unit * mass + tol
            for read, out in (("kernel", got), ("masked", want)):
                share[read] = max(share[read], float(
                    (np.abs(out[layer] - truth) / allowed).max()))
        err = float(np.abs(got - want).max())
        _require(np.isfinite(got).all() and share["kernel"] <= 1.0
                 and not got[:, ~live].any(),
                 f"eva_read {name}: {share['kernel']} of the rounding's "
                 f"bound from the true read, or a free slot not zeros")
        rows = (kernel.fetched_rows(np.asarray(pos) % window + 1)
                + kernel.fetched_rows((np.asarray(pos) // window)
                                      * (window // chunk)))[live]
        fetched = int(rows.sum()) * 2 * heads * head_dim \
            * np.dtype(dtype).itemsize
        rec = {"error": err, "bound_share": share,
               "rows_fetched_mean": float(rows.mean()),
               "masked_ms": timed(twin_fn, qs, stack, pos, live_d),
               "kernel_ms": timed(own_fn, qs, stack, pos, live_d)}
        rec["kernel_gb_s"] = (round(fetched / rec["kernel_ms"] / 1e6, 1)
                              if reps else None)
        cases[name] = rec
        print(f"chip_smoke: eva_read {name}: {json.dumps(rec)}", flush=True)
    return {"ok": True, "setup_s": round(time.perf_counter() - t_start, 2),
            "tolerance": tol, "selected": selected, "cases": cases}


# held like LATENT_TOL: the root mean square of the logit differences over
# every checked position and the widest single one. On the chip the leg
# read 0.0074 and 0.187 on logits that spread by 1.28 (bfloat16 operands
# against true float32: a rounding turns an expert at the edge of its 22
# now and then); a state that is not carried, or carried from the wrong
# position, moves every logit of a position by a good part of their
# spread, which is what the mean square holds
SSM_TOL = {"rms": 0.05, "widest": 1.0}


def family_state_init(params, seed=39):
    """``params`` with every Mamba layer's ``A_log``, ``dt_bias`` and ``D``
    set as the Nemotron-H family initialises them (``A`` uniform in [1,
    16], ``dt`` log-uniform in [1e-3, 0.1] through the inverse softplus,
    ``D`` 1): a state that remembers hundreds of positions, where the
    benchmark's draw (``A ~ -1``, ``dt ~ 0.7``) forgets within a few."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)

    def fill(layer):
        m = layer["mixer"]
        if "A_log" not in m:
            return layer
        h, dt = m["A_log"].shape[0], m["A_log"].dtype
        step = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), h))
        m = dict(m, A_log=jnp.asarray(np.log(rng.uniform(1, 16, h)), dt),
                 dt_bias=jnp.asarray(step + np.log(-np.expm1(-step)), dt),
                 D=jnp.ones((h,), dt))
        return dict(layer, mixer=m)

    return dict(params, layers=[fill(l) for l in params["layers"]])


# the leg's model: one Mamba and one expert layer at the published widths,
# every other argument the model's default (prompt blocks of 768 among
# them); three blocks of positions, room for the longer prompt's steps
SSM_LEG_KW = dict(vocab_size=2048, hybrid_override_pattern="ME",
                  experts_held=64, max_position=3 * 768)


def ssm_leg(model_kw=None, lengths=(1000, 1300), steps=64, dtype="bfloat16",
            tol=SSM_TOL, weights_spec=None):
    """One Mamba-2 layer and one expert layer of ``models/nemotron_h.py``
    at the published widths (a latent expert layer of 64 held of 512, 22 a
    token), the family's own state initialisation: two streams of
    ``lengths`` are prefilled (two blocks of 768 each, several chunks of
    128, a ragged last one) and decoded for ``steps`` steps through the slot table, the state
    updated by ``ops/ssm_step.py`` where it applies, and after the prefill
    and after every step each stream's logits are held to
    ``benchmarks/reference/nemotron3.py``'s rows of the whole sequence
    (true float32, the literal recurrence). ``tol`` holds the root mean
    square and the widest of the logit differences; both are reported."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import weights
    from benchmarks.reference import nemotron3 as reference_mod
    from bigdl_tpu.models.nemotron_h import NemotronHForCausalLM
    from bigdl_tpu.ops import ssm_step
    from bigdl_tpu.serving.slots import SlotManager

    kw = dict(SSM_LEG_KW, **(model_kw or {}))
    t_start = time.perf_counter()
    model = NemotronHForCausalLM(**kw)
    import inspect
    defaults = {k: v.default for k, v in inspect.signature(
        NemotronHForCausalLM.__init__).parameters.items()
        if v.default is not inspect.Parameter.empty}
    ref_kw = dict(defaults, **kw)
    shapes = jax.eval_shape(lambda k: model.setup(k, None)[0],
                            jax.random.key(0))
    params = family_state_init(weights.make_params(
        shapes, 39, weights_spec or {"std": 0.02, "gain_std": 0.1,
                                     "bias_std": 0.01}, dtype=dtype))
    reference, _ = reference_mod.make({"constructor_kwargs": ref_kw})
    pmax = kw["max_position"]
    rng = np.random.default_rng(39)
    seqs = [rng.integers(0, kw["vocab_size"], pmax).astype(np.int32)
            for _ in lengths]
    sm = SlotManager(model, params, max_slots=2, window=1)
    slots = [sm.admit([s[:n]])[0] for s, n in zip(seqs, lengths)]
    want = [np.asarray(reference(
        params, s, np.arange(n - 1, n + steps, dtype=np.int32)))
        for s, n in zip(seqs, lengths)]
    worst, squares, spread = 0.0, [], float(np.std(want[0]))
    with _compile_log() as compiles:
        for step in range(steps + 1):
            if step == 2:
                steady_from = time.perf_counter()
            got = np.asarray(sm._logits, np.float32)
            for slot, w in zip(slots, want):
                gap = np.abs(got[slot] - w[step])
                worst = max(worst, float(gap.max()))
                squares.append(float(np.square(gap, dtype=np.float64).mean()))
            if step == steps:
                break
            # feed the sequence's own next token: plant it as the only
            # finite logit of the slot's row
            forced = np.full(got.shape, -np.inf, np.float32)
            for slot, s, n in zip(slots, seqs, lengths):
                forced[slot, s[n + step]] = 0.0
            sm._logits = jnp.asarray(forced, sm._logits.dtype)
            sm.step()
    late = [name for t, name in compiles if steps >= 2 and t >= steady_from]
    _require(not late, f"ssm: compiled inside the steady steps: {late}")
    rms = float(np.sqrt(np.mean(squares)))
    _require(rms <= tol["rms"] and worst <= tol["widest"],
             f"ssm: the logits lie {rms} (root mean square; widest "
             f"{worst}) from the reference's, over {tol} (spread {spread})")
    state = sm._cache[0]["ssm"]
    return {"ok": True, "setup_s": round(time.perf_counter() - t_start, 2),
            "tolerance": tol, "logit_gap": worst, "logit_gap_rms": rms,
            "logit_spread": spread, "lengths": list(lengths), "steps": steps,
            "ssm_update": "kernel" if ssm_step.applies(state) else "plain",
            "ssm_slots": int(sm.stats["ssm_slots"])}


# ------------------------------------------------------------------ train --
def train_leg(model, x_shape, n_class, steps, compute_dtype, seed=0):
    """A few optimizer steps on one repeated seeded batch through the
    public ``Optimizer`` on ``Engine.create_mesh()`` (all devices): loss
    finite at every step and lower at the end than at the start. Returns
    the record. Optimizer shards and the batch must span the mesh (on
    one chip that is trivially so; on a four-chip host it is the check
    that nothing was left on device 0)."""
    import jax

    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import DataSet, SampleToMiniBatch
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.optim import SGD, Optimizer, Trigger
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.visualization.summary import TrainSummary

    rng = np.random.default_rng(seed)
    x = rng.standard_normal(x_shape).astype(np.float32)
    y = rng.integers(0, n_class, x_shape[0]).astype(np.int32)
    ds = DataSet.array([Sample(x[i], y[i]) for i in range(x_shape[0])]) \
        >> SampleToMiniBatch(x_shape[0])

    stamps = []

    class Timed(TrainSummary):
        def add_scalar(self, tag, value, step):
            if tag == "Loss":
                stamps.append(time.perf_counter())
            return super().add_scalar(tag, value, step)

    with tempfile.TemporaryDirectory() as logdir:
        summary = Timed(logdir, "chip_smoke")
        opt = Optimizer(model=model, dataset=ds,
                        criterion=nn.ClassNLLCriterion(),
                        mesh=Engine.create_mesh(),
                        compute_dtype=compute_dtype)
        opt.set_optim_method(SGD(learningrate=0.01, momentum=0.9))
        opt.set_train_summary(summary)
        opt.set_end_when(Trigger.max_iteration(steps))
        t0 = time.perf_counter()
        with _compile_log() as compiled:
            opt.optimize()
        losses = [v for _, v in summary.read_scalar("Loss")]
        summary.close()
    _require(len(losses) == steps, f"{len(losses)} losses for {steps} steps")
    _require(np.isfinite(losses).all(), f"loss not finite: {losses}")
    _require(losses[-1] < losses[0], f"loss did not fall: {losses}")
    late = [name for t, name in compiled if stamps[0] < t < stamps[-1]]
    _require(not late, f"compiled between the first and the last step: "
                       f"{late}")
    n = int(opt.mesh.devices.size)
    _spans([v for v in jax.tree_util.tree_leaves(opt._opt_state)
            if v.ndim], n, "optimizer shards")
    _spans(opt._shard_batch(next(iter(ds.data(train=False)))), n, "batch")
    return {"ok": True, "steps": steps, "setup_s": round(stamps[0] - t0, 2),
            "steady_s": round(stamps[-1] - stamps[0], 2),
            "loss_first": round(losses[0], 4),
            "loss_last": round(losses[-1], 4), "mesh_devices": n,
            "feed_wait_frac": round(
                opt.metrics_summary()["feed_wait_frac"], 3)}


def train_resnet50(steps=8, batch=256):
    import jax.numpy as jnp

    from bigdl_tpu.models.resnet import ResNet
    return train_leg(ResNet(class_num=1000, depth=50, format="NHWC"),
                     (batch, 224, 224, 3), 1000, steps, jnp.bfloat16)


# ------------------------------------------------------------- four chips --
def memory_leg():
    """Every chip holds something: code that has only ever run on a
    virtual CPU mesh may have put everything on device 0."""
    import jax
    used = [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]
    _require(min(used) > 0, f"a chip was never used: {used}")
    return {"ok": True, "peak_bytes_in_use": used}


def dryrun_leg(n):
    import __graft_entry__
    t0 = time.perf_counter()
    __graft_entry__.dryrun_multichip(n)
    return {"ok": True, "setup_s": round(time.perf_counter() - t0, 2)}


# ------------------------------------------------------------------- main --
def _run_leg(fn):
    """A failed leg is printed and recorded under its own name, and the
    run goes on so one call to the chip reports every failure."""
    try:
        return fn()
    except Exception as e:
        traceback.print_exc()
        return {"ok": False, "error": f"{type(e).__name__}: {e}"[:2000]}


def native_leg():
    """The host kernels are built on this machine from
    csrc/bigdl_tpu_native.cpp (the checkout carries no binary) and give
    the same CRC32C as the Python fallback."""
    from bigdl_tpu.utils import native
    from bigdl_tpu.visualization.tensorboard import _crc32c_py

    had = os.path.exists(native._SO)
    lib = native.native_lib()
    _require(lib is not None, "native library unavailable")
    data = bytes(range(256)) * 33
    _require(lib.crc32c_bytes(data) == _crc32c_py(data), "crc32c mismatch")
    return {"ok": True,
            "library": "found built" if had else "built on this machine"}


def _cache_record(cache_dir, before):
    if not cache_dir:
        return {"dir": None}
    files = [os.path.join(cache_dir, f) for f in os.listdir(cache_dir)]
    return {"dir": cache_dir, "entries_before": before,
            "entries_added": len(files) - before,
            "mbytes": round(sum(map(os.path.getsize, files)) / 2 ** 20, 1)}


def require_tpu():
    """Force the platform so a missing chip raises instead of JAX quietly
    choosing the CPU; returns the device record."""
    import jax

    from bigdl_tpu.utils.engine import Engine
    asked = os.environ.get("JAX_PLATFORMS")
    try:
        Engine.init("tpu")
    except RuntimeError as e:
        raise SystemExit(
            f"chip_smoke.py runs on a TPU only and JAX found none "
            f"(JAX_PLATFORMS was {asked!r}): {e}")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke.py runs on a TPU only; JAX found "
                         f"platform {dev.platform!r}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def report(device, legs, cache):
    """Print the record and then the verdict; returns the exit code. The
    verdict, the last line, holds ``ok`` and ``device`` and no other key:
    the driver's check reads it, the record before it is for people."""
    import jax
    import jaxlib
    from importlib import metadata
    ok = all(rec["ok"] for rec in legs.values())
    print("chip_smoke: report: " + json.dumps({
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": metadata.version("libtpu")},
        "cache": cache, "legs": legs}))
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


def main():
    device = require_tpu()
    import jax

    from bigdl_tpu.models.gpt import gpt2_small
    from bigdl_tpu.ops.pallas_util import use_interpret
    if use_interpret():
        raise SystemExit("the Pallas kernels would run interpreted")
    cache_dir = jax.config.jax_compilation_cache_dir
    before = len(os.listdir(cache_dir)) if cache_dir else 0
    print(f"chip_smoke: {device}, compile cache {cache_dir} "
          f"({before} entries)", flush=True)

    params, _ = gpt2_small().setup(jax.random.key(0), None)
    kernel_flags = ("BIGDL_TPU_PAGED_KERNEL",)
    plan = [
        ("native", native_leg),
        ("serve", lambda: serve_leg({}, params, GPT2_PROMPT_WAVES,
                                    GPT2_NEW_TOKENS, MARGIN_TOL)),
        ("serve_kernels", lambda: serve_leg(
            {}, params, GPT2_PROMPT_WAVES, GPT2_NEW_TOKENS, MARGIN_TOL,
            engine_kw={"paged": True}, flags=kernel_flags)),
        ("serve_kernels_int8_kv", lambda: serve_leg(
            {}, params, GPT2_PROMPT_WAVES, GPT2_NEW_TOKENS,
            MARGIN_TOL_INT8_KV, engine_kw={"paged": True, "int8_kv": True},
            flags=kernel_flags)),
        ("kernels", kernels_leg),
        ("kv_write", kv_write_leg),
        ("decode_attention", decode_attention_leg),
        ("sampling", sampling_leg),
        ("latent_read", latent_read_leg),
        ("eva_read", eva_read_leg),
        ("ssm", ssm_leg),
        ("grouped_product", grouped_product_leg),
        ("train", train_resnet50),
    ]
    if device["count"] >= 4:
        # the train leg above already ran on Engine.create_mesh() over
        # every device; these add tensor-parallel serving and the dry run
        plan += [
            ("serve_tp4", lambda: serve_leg(
                {}, params, GPT2_PROMPT_WAVES, GPT2_NEW_TOKENS, MARGIN_TOL,
                engine_kw={"tp": 4})),
            ("dryrun_multichip", lambda: dryrun_leg(4)),
            ("memory_on_every_chip", memory_leg),
        ]
    legs = {}
    for name, fn in plan:
        t0 = time.perf_counter()
        legs[name] = _run_leg(fn)
        legs[name]["wall_s"] = round(time.perf_counter() - t0, 2)
        print(f"chip_smoke: {name}: {json.dumps(legs[name])}", flush=True)

    return report(device, legs, _cache_record(cache_dir, before))


if __name__ == "__main__":
    sys.exit(main())
