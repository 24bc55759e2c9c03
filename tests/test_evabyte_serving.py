"""EVA attention through the model protocol: a window table that starts
over and chunk summaries beside it, in the dense slot table. EvaByte at a
small size on the CPU (hidden 64, 4 heads of 16, window 32, chunk 4, 2
layers, 8 heads of a 40-wide vocabulary), seeded random float32 weights,
held to ``benchmarks/reference/evabyte.py`` (which imports nothing of the
program).

Tolerance: float32 throughout, so the program and the reference differ
only in the ORDER of float32 sums (a softmax in two parts, a block of
queries at a time, against one over the joined columns; a cached step
against a whole pass): at most 1e-5 on logits of up to 7 units. 5e-5
leaves five times that and would not pass a wrong row, window or summary:
either planted fault moves a logit past the first window by 4 to 8."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from benchmarks.harness import weights
from benchmarks.reference import evabyte as reference_mod
from bigdl_tpu import obs
from bigdl_tpu.obs import reqtrace
from bigdl_tpu.models.evabyte import EvaByteForCausalLM
from bigdl_tpu.models.gpt import GPTForCausalLM, prompt_bucket
from bigdl_tpu.models.lfm2 import LFM2ForCausalLM
from bigdl_tpu.serving import ServingEngine
from bigdl_tpu.serving import slots as slots_mod
from bigdl_tpu.serving.slots import SlotManager, select_tokens

TOL = 5e-5
KW = dict(vocab_size=40, hidden_size=64, intermediate_size=96,
          num_hidden_layers=2, num_attention_heads=4, window_size=32,
          chunk_size=4, num_pred_heads=8, max_position=128)
W, C, PMAX = KW["window_size"], KW["chunk_size"], KW["max_position"]
SPEC = {"std": 0.2, "gain_mean": 0.0, "gain_std": 0.1, "bias_std": 1.0}
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def eva():
    model = EvaByteForCausalLM(**KW)
    shapes = jax.eval_shape(lambda k: model.setup(k, None)[0],
                            jax.random.key(0))
    params = weights.make_params(shapes, 5, SPEC)
    reference, faults = reference_mod.make({
        "constructor_kwargs": KW, "controls": [],
        "faults": list(reference_mod.FAULTS)})
    return model, params, reference, faults


def _reference_rows(reference, params, seq):
    ids = np.zeros(PMAX, np.int32)
    ids[:len(seq)] = seq
    return np.asarray(reference(params, ids, np.arange(PMAX, dtype=np.int32)))


def _all_heads(params, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference_mod.forward_logits(
            params, jnp.asarray(ids), np.arange(len(ids)), KW, heads="all"))


# (a) the whole pass against the reference, every head ----------------------
@pytest.mark.parametrize("length", [3, 4, 31, 33, 70, 97, 128])
def test_apply_is_the_references_whole_pass_on_all_eight_heads(eva, length):
    """Lengths that are no multiple of the chunk or the window, inside
    the first window and three windows deep."""
    model, params, _, _ = eva
    ids = np.random.default_rng(length).integers(0, 40, length).astype(
        np.int32)
    got = np.asarray(model.apply(params, (), ids[None])[0])
    assert got.shape == (length, 8 * 40)
    assert np.abs(got - _all_heads(params, ids)).max() < TOL


def test_two_rows_of_a_batch_are_two_sequences(eva):
    model, params, reference, _ = eva
    ids = np.random.default_rng(2).integers(0, 40, (2, 75)).astype(np.int32)
    got = np.asarray(model.apply(params, (), ids)[0]).reshape(2, 75, -1)
    for row in range(2):
        want = _reference_rows(reference, params, ids[row])[:75]
        assert np.abs(got[row, :, :40] - want).max() < TOL


# (d) the planted faults fail the same comparison ---------------------------
@pytest.mark.parametrize("fault", reference_mod.FAULTS)
def test_a_planted_fault_fails_at_the_tolerance(eva, fault):
    model, params, reference, faults = eva
    ids = np.random.default_rng(9).integers(0, 40, 100).astype(np.int32)
    got = np.asarray(model.apply(params, (), ids[None])[0])[:, :40]
    wrong = np.asarray(faults["fault:" + fault](
        params, np.pad(ids, (0, PMAX - 100)), np.arange(100)))
    gap = np.abs(got - wrong).max(-1)
    # nothing of the mechanism is in play inside the first window
    assert gap[:W].max() < TOL
    assert gap[W:].max() > 1000 * TOL


# (b) prefill then decoding through the slot table against the whole pass --
@pytest.mark.parametrize("lengths", [(1, 5), (31, 32, 33), (4, 37, 64, 70)])
def test_prefill_then_steps_agree_with_reference(eva, lengths):
    """Rows padded to their bucket, prompts that end inside the first
    window, on a window's edge and in the third window: every slot's
    logits after the prefill and after each of 58 steps (which cross two
    window boundaries and close 14 chunks a slot) are the reference's at
    that position of the whole sequence."""
    model, params, reference, _ = eva
    rng = np.random.default_rng(sum(lengths))
    steps = PMAX - max(lengths) - 1
    assert steps >= 2 * W - 7
    seqs = [rng.integers(0, 40, PMAX).astype(np.int32) for _ in lengths]
    sm = SlotManager(model, params, max_slots=5, window=4)
    slots = sm.admit([s[:n] for s, n in zip(seqs, lengths)])
    want = [_reference_rows(reference, params, s) for s in seqs]
    for step in range(steps + 1):
        got = np.asarray(sm._logits)
        for slot, w, n in zip(slots, want, lengths):
            assert np.abs(got[slot] - w[n - 1 + step]).max() < TOL, (step, n)
        if step == steps:
            break
        # feed the sequence's own next byte, not the argmax: plant it as
        # the only finite logit of the slot's row
        forced = np.full(got.shape, -np.inf, np.float32)
        for slot, s, n in zip(slots, seqs, lengths):
            forced[slot, s[n + step]] = 0.0
        sm._logits = jnp.asarray(forced)
        sm.step()
    assert sm.stats["step_traces"] == 1


def test_engine_serves_streams_admitted_at_different_times(eva):
    """Five prompts of different lengths through two slots: later ones
    are admitted as earlier ones retire, each decodes across two window
    boundaries, and every served byte is the reference's best (or within
    the tolerance of it) at its position of the whole sequence."""
    model, params, reference, _ = eva
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 40, n).astype(np.int32)
               for n in (3, 30, 45, 17, 33)]
    with ServingEngine(model, params, max_slots=2, max_queue=8,
                       prefill_window=2) as eng:
        outs = [h.result(timeout=300)
                for h in [eng.submit(p, 70) for p in prompts]]
        assert eng.slots.kv_write == "scatter"
        assert eng.slots.attn_read == "masked"
    for p, o in zip(prompts, outs):
        o = np.asarray(o)
        assert len(o) == len(p) + 70
        rows = _reference_rows(reference, params, o)[len(p) - 1:len(o) - 1]
        assert (rows.max(-1) - rows[np.arange(70), o[len(p):]]).max() < TOL


# (c) a reused slot reads nothing of its former occupant --------------------
def test_a_reused_slot_reads_nothing_of_its_former_occupant(eva):
    """A stream retired deep in its third window leaves a full window
    table and 20 summaries behind. The freed rows are poisoned with NaN
    (one NaN read would make every logit NaN); a 3-byte prompt takes the
    slot and decodes into its second window beside a stream that was
    live all along."""
    model, params, reference, _ = eva
    rng = np.random.default_rng(3)
    old, stays, new = (rng.integers(0, 40, PMAX).astype(np.int32)
                       for _ in range(3))
    sm = SlotManager(model, params, max_slots=2, window=2)
    gone, kept = sm.admit([old[:70], stays[:9]])

    def force_and_step(pairs):
        forced = np.full((2, 40), -np.inf, np.float32)
        for slot, tok in pairs:
            forced[slot, tok] = 0.0
        sm._logits = jnp.asarray(forced)
        sm.step()

    for i in range(12):
        force_and_step([(gone, old[70 + i]), (kept, stays[9 + i])])
    assert sm.lengths[gone] == 82 and 82 // W == 2
    sm.retire(gone)
    sm._cache = jax.tree_util.tree_map(
        lambda leaf: leaf.at[gone].set(jnp.nan), sm._cache)
    assert sm.admit([new[:3]]) == [gone]
    want_new = _reference_rows(reference, params, new)
    want_kept = _reference_rows(reference, params, stays)
    for i in range(40):
        got = np.asarray(sm._logits)
        assert np.isfinite(got).all()
        assert np.abs(got[gone] - want_new[2 + i]).max() < TOL
        assert np.abs(got[kept] - want_kept[20 + i]).max() < TOL
        force_and_step([(gone, new[3 + i]), (kept, stays[21 + i])])


# (e) the engine refuses what the model does not carry, by name ------------
@pytest.mark.parametrize("feature, kwargs", [
    ("paged", dict(paged=True)),
    ("spec_tokens", dict(spec_tokens=4)),
    ("lora", dict(lora=True)),
    ("int8_weights", dict(int8_weights=True)),
    ("int8_kv", dict(int8_kv=True)),
    ("tp", dict(tp=2)),
    ("kv_snapshot", dict(kv_snapshot=True, snapshot_dir="unused")),
])
def test_engine_refuses_a_feature_the_model_does_not_carry(eva, feature,
                                                           kwargs):
    model, params, _, _ = eva
    with pytest.raises(TypeError, match=f"'{feature}'"):
        ServingEngine(model, params, max_slots=2, **kwargs)


# (f) the spans carry the host's own arithmetic -----------------------------
def test_the_model_describes_its_two_tables(eva):
    model, _, _, _ = eva
    near, far = model.cache_tables()
    assert (near.leaves, near.rows, near.row_axis) == (("win_k", "win_v"), W, 1)
    assert (far.leaves, far.rows, far.row_axis) == (("sum_k", "sum_v"), PMAX // C, 1)
    pos = np.arange(PMAX)
    assert (near.write_row(pos) == pos % W).all()
    assert (near.read_rows(pos) == pos % W + 1).all()
    assert (far.write_row(pos) == np.where(pos % C == C - 1, pos // C,
                                           -1)).all()
    assert (far.read_rows(pos) == (W // C) * (pos // W)).all()
    cache = model.init_cache(3, jnp.float32)
    assert [sorted(c) for c in cache] == [
        ["sum_k", "sum_v", "win_k", "win_v"]] * 2
    assert cache[0]["win_k"].shape == (3, W, 4, 16)
    assert cache[0]["sum_v"].shape == (3, PMAX // C, 4, 16)


def test_step_and_prefill_spans_carry_the_rows_the_host_reckons(eva):
    model, params, _, _ = eva
    sm = SlotManager(model, params, max_slots=4, window=2)
    sm.admit([np.zeros(n, np.int32) for n in (5, 45)])
    assert sm.prefill_attrs == {"eva_windows": 1 + 2, "eva_chunks": 1 + 11}
    sums = {"eva_window_rows": 0, "eva_summary_rows": 0,
            "eva_chunks_closed": 0}
    for step in range(40):
        if step == 10:
            sm.admit([np.zeros(70, np.int32)])
        if step == 25:
            sm.retire(0)
        pos = sm.lengths[sm.active].astype(int)
        want = {"eva_window_rows": int(sum(p % W + 1 for p in pos)),
                "eva_summary_rows": int(sum((W // C) * (p // W)
                                            for p in pos)),
                "eva_chunks_closed": int(sum(p % C == C - 1 for p in pos))}
        table = 4 * (1 + 1)          # 32 and 32 rows a slot, a block each
        assert sm.attn_blocks() == (table, table)
        sm.step()
        assert sm.step_attrs == want
        for name, n in want.items():
            sums[name] += n
    assert sums["eva_chunks_closed"] > 0 and sums["eva_summary_rows"] > 0
    assert {k: sm.stats[k] for k in sums} == sums
    assert sm.stats["eva_windows"] == 6 and sm.stats["eva_chunks"] == 29


def test_engine_stamps_the_rows_on_its_spans_and_sums_them(eva):
    model, params, _, _ = eva
    obs.default_tracer().clear()
    rng = np.random.default_rng(4)
    with ServingEngine(model, params, max_slots=2, max_queue=8) as eng:
        for h in [eng.submit(rng.integers(0, 40, n).astype(np.int32), 40)
                  for n in (6, 50, 21)]:
            h.result(timeout=300)
        stats = dict(eng.stats)
    spans = obs.default_tracer().spans()
    # the spans that dispatch a block describe it; the last of a busy
    # stretch only reads one back (docs/observability.md)
    steps = [s.attrs for s in spans
             if s.name == "serve/step" and "live" in s.attrs]
    fills = [s.attrs for s in spans if s.name == "serve/prefill"]
    for name in ("eva_window_rows", "eva_summary_rows", "eva_chunks_closed"):
        assert stats[name] == sum(a[name] for a in steps)
    for name in ("eva_windows", "eva_chunks"):
        assert stats[name] == sum(a[name] for a in fills)
    assert all(a["live"] <= a["eva_window_rows"] <= W * a["live"]
               for a in steps)
    assert stats["eva_windows"] == 1 + 2 + 1
    assert stats["eva_chunks"] == 1 + 12 + 5


def test_a_model_of_one_table_stamps_none_of_it():
    obs.default_tracer().clear()
    model = GPTForCausalLM(vocab_size=61, hidden_size=32, n_layers=2,
                           n_heads=4, max_position=64)
    params = model.setup(jax.random.key(0), None)[0]
    with ServingEngine(model, params, max_slots=2) as eng:
        eng.submit(np.arange(5, dtype=np.int32), 4).result(timeout=120)
        assert not [k for k in eng.stats if k.startswith("eva_")]
    for s in obs.default_tracer().spans():
        if s.name in ("serve/step", "serve/prefill"):
            assert not [k for k in s.attrs if k.startswith("eva_")]


# (g) GPT-2's and LFM2's executables are the programs they were -------------
def _pair_before_the_description(sm, in_place, bounded):
    """``SlotManager._build_fns`` as it stood before a model described
    its cache: the write and the read spelled for the one table of K and
    V, ``read = where(active, pos + 1, 0)`` (the dense, unlayouted,
    pool-less pair). GPT-2's step is handed the table's mask too, as
    LFM2's always was: the write kernel takes it as an operand since
    PR 33."""
    model, stats = sm.model, sm.stats
    top_k, top_p, sampler, pmax, n_steps = (
        sm.top_k, sm.top_p, sm.sampler, sm.max_position, sm.steps_per_sync)
    cache_dtype = sm._dtype
    routed = bool(model.experts_per_token)

    def prefill(params, cache, logits_buf, ids, prompt_len, slot_idx):
        stats.tick("prefill_traces")
        tmp = model.init_cache(ids.shape[0], cache_dtype)
        h_last, tmp = model.prefill(params, tmp, ids, prompt_len)
        rows = model.logits(params, h_last)
        cache = jax.tree_util.tree_map(
            lambda c, t: c.at[slot_idx].set(t), cache, tmp)
        logits_buf = logits_buf.at[slot_idx].set(
            rows.astype(logits_buf.dtype))
        return cache, logits_buf

    def step(params, cache, logits_buf, lengths, active, temps, key):
        stats.tick("step_traces")

        def one(carry, _):
            cache, logits, lengths, key = carry
            tok, key = select_tokens(logits, temps, key, top_k, top_p,
                                     sampler)
            pos = jnp.minimum(lengths, pmax - 1)
            read = jnp.where(active, pos + 1, 0) if bounded else None
            if routed:
                h, cache, hit = model.decode_step(
                    params, cache, tok, pos, in_place=in_place,
                    live=active, read=read)
                tok = (tok, hit)
            else:
                h, cache = model.decode_step(params, cache, tok, pos,
                                             in_place=in_place, live=active,
                                             read=read)
            logits = model.logits(params, h).astype(logits.dtype)
            lengths = lengths + active.astype(lengths.dtype)
            return (cache, logits, lengths, key), tok

        lengths = jnp.asarray(lengths, jnp.int32)
        (cache, logits_buf, _, key), toks = jax.lax.scan(
            one, (cache, logits_buf, lengths, key), None, length=n_steps)
        return cache, logits_buf, key, toks

    return {"jit_prefill": jax.jit(prefill, donate_argnums=(1, 2)),
            "jit_step": jax.jit(step, donate_argnums=(1, 2, 6))}


def _gpt2():
    model = GPTForCausalLM(vocab_size=61, hidden_size=32, n_layers=2,
                           n_heads=4, max_position=128)
    return model, model.setup(jax.random.key(0), None)[0]


def _lfm2():
    model = LFM2ForCausalLM(
        vocab_size=97, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=24,
        layer_types=["conv", "full_attention", "conv", "conv"],
        num_dense_layers=1, num_experts=8, num_experts_per_tok=2,
        num_attention_heads=4, num_key_value_heads=2, max_position=128)
    return model, model.setup(jax.random.key(0), None)[0]


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["scatter-masked", "both-kernels"])
@pytest.mark.parametrize("which", ["jit_prefill", "jit_step"])
@pytest.mark.parametrize("family", [_gpt2, _lfm2], ids=["gpt2", "lfm2"])
def test_one_table_models_lower_to_the_same_text(family, which, kernels,
                                                 monkeypatch):
    """The pair built from the model's description against the pair
    spelled for K and V by name: the same text, with the plain write and
    the masked read (what a CPU table selects) and with both kernels (the
    table's word overridden, as on the chip)."""
    monkeypatch.setattr(reqtrace, "_trace_on", False)
    if kernels:
        monkeypatch.setattr(slots_mod, "in_place_applies", lambda *a: True)
        monkeypatch.setattr(slots_mod.decode_attention, "applies",
                            lambda *a: True)
    model, params = family()
    sm = SlotManager(model, params, 4, window=2, top_k=5, top_p=0.9)
    assert (sm.kv_write, sm.attn_read) == (
        ("kernel", "kernel") if kernels else ("scatter", "masked"))
    old = _pair_before_the_description(sm, kernels, kernels)[which]
    if which == "jit_prefill":
        args = (sm.params, sm._cache, sm._logits,
                np.zeros((2, prompt_bucket(9, 128)), np.int32),
                np.ones(2, np.int32), np.array([0, 4], np.int32))
        new = sm._prefill_fn
    else:
        args = (sm.params, sm._cache, sm._logits, sm.lengths, sm.active,
                sm.temps, sm._key)
        new = sm._step_fn
    a, b = old.lower(*args).as_text(), new.lower(*args).as_text()
    assert a.replace("jit_" + old.__name__, which) == b


def test_no_leaf_is_looked_for_by_name():
    """A model whose state is all fixed-size describes no table: the
    slot table builds its pair, keeps the plain write and the masked
    read, and counts no block."""
    model = LFM2ForCausalLM(
        vocab_size=31, hidden_size=16, intermediate_size=24,
        moe_intermediate_size=8, layer_types=["conv", "conv"],
        num_dense_layers=2, num_experts=4, num_experts_per_tok=1,
        num_attention_heads=2, num_key_value_heads=1, max_position=32)
    params = model.setup(jax.random.key(0), None)[0]
    assert model.cache_tables() == ()
    sm = SlotManager(model, params, 2, window=1)
    assert (sm.kv_write, sm.attn_read) == ("scatter", "masked")
    assert sm.attn_blocks() == (0, 0)
    slot, = sm.admit([np.arange(5, dtype=np.int32)])
    sm.step()
    assert sm.lengths[slot] == 6


def test_a_model_that_does_not_describe_its_cache_is_refused():
    class Silent:
        vocab_size = max_position = 8
        serving_features = frozenset()
        serving_dtype = init_cache = prefill = decode_step = logits = None

    with pytest.raises(TypeError, match="cache_tables"):
        ServingEngine(Silent(), params={}, max_slots=1)


# the cell's buckets -------------------------------------------------------
def test_the_cells_prompts_take_the_five_buckets_its_warm_up_builds():
    """A 12 288-byte prompt takes the 16 384 bucket, and every prompt
    length of ``evabyte-longdoc-generate`` takes one of five prefill
    executables, 1024 to 16 384: the power-of-two class that the
    benchmark's warm-up runs the longest prompt of, so the window
    compiles none (``window_compiles`` 0, which the chip run reads)."""
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           "evabyte-longdoc-generate.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "evabyte-6.5b-serve.json")) as f:
        pmax = json.load(f)["constructor_kwargs"]["max_position"]
    lo, hi = (traffic["prompt_tokens"][k] for k in ("min", "max"))
    assert (lo, hi, pmax) == (1024, 12288, 16384)
    assert prompt_bucket(12288, pmax) == 16384
    assert hi + traffic["output_tokens"]["max"] <= pmax
    buckets = {prompt_bucket(n, pmax) for n in range(lo, hi + 1)}
    assert buckets == {1024, 2048, 4096, 8192, 16384}
    # the warm-up's class of a length is the bucket the table pads it to
    assert all(max(16, 1 << (n - 1).bit_length()) == prompt_bucket(n, pmax)
               for n in range(lo, hi + 1, 97))
    model = EvaByteForCausalLM(**dict(KW, max_position=pmax,
                                      window_size=2048, chunk_size=16))
    assert model.prefill_counts(np.array([12288]))["eva_windows"] == 6


def test_unit_offset_norm_is_one_plus_g():
    norm = nn.RMSNorm(8, 1e-5, unit_offset=True)
    p = norm.make_params(None, None)
    assert np.all(np.asarray(p["weight"]) == 0.0)
    x = jax.random.normal(jax.random.key(0), (3, 8))
    plain = nn.RMSNorm(8, 1e-5)
    assert np.allclose(norm.call(p, x),
                       plain.call(plain.make_params(None, None), x))
    g = {"weight": jnp.full((8,), 0.5)}
    assert np.allclose(norm.call(g, x), 1.5 * norm.call(p, x))
