"""The model protocol, the two kinds of slot state, and the dropless expert
layer: LFM2-MoE at a small size on the CPU, seeded random float32 weights,
held to ``benchmarks/reference/lfm2moe.py`` (which imports nothing of the
program). Tolerances: float32 throughout, so the program and the
reference differ only in the ORDER of float32 sums (a grouped product
against a masked dense one, a cached step against a whole pass): a few
1e-6 on logits of a few units; 5e-5 leaves an order of magnitude and
would not pass a wrong tap, position or expert (those move logits by
0.01 and more)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from benchmarks.harness import weights
from benchmarks.reference import lfm2moe
from bigdl_tpu import obs
from bigdl_tpu.obs import reqtrace
from bigdl_tpu.models.gpt import GPTForCausalLM, prompt_bucket
from bigdl_tpu.models.lfm2 import LFM2ForCausalLM
from bigdl_tpu.serving import ServingEngine
from bigdl_tpu.serving.slots import SlotManager, select_tokens

TOL = 5e-5
KW = dict(vocab_size=97, hidden_size=32, intermediate_size=48,
          moe_intermediate_size=24,
          layer_types=["conv", "full_attention", "conv", "conv"],
          num_dense_layers=1, num_experts=8, num_experts_per_tok=2,
          num_attention_heads=4, num_key_value_heads=2, conv_L_cache=3,
          max_position=64)
SPEC = {"std": 0.2, "gain_std": 0.1, "bias_std": 0.1}


@pytest.fixture(scope="module")
def lfm2():
    model = LFM2ForCausalLM(**KW)
    shapes = jax.eval_shape(lambda k: model.setup(k, None)[0],
                            jax.random.key(0))
    params = weights.make_params(shapes, 5, SPEC)
    reference, _ = lfm2moe.make({"constructor_kwargs": KW, "controls": []})
    return model, params, reference


def _reference_rows(reference, params, seq):
    ids = np.zeros(KW["max_position"], np.int32)
    ids[:len(seq)] = seq
    rows = np.arange(KW["max_position"], dtype=np.int32)
    return np.asarray(reference(params, ids, rows))


# (a) prefill then decoding through the slot table against the whole pass --
@pytest.mark.parametrize("lengths", [(1, 2), (3, 17), (2, 16, 9, 31)])
def test_prefill_then_steps_agree_with_reference(lfm2, lengths):
    """Rows padded to their bucket beside prompts shorter than the three
    taps: every slot's logits after the prefill and after each of 6 steps
    are the reference's at that position of the whole sequence."""
    model, params, reference = lfm2
    rng = np.random.default_rng(sum(lengths))
    seqs = [rng.integers(0, KW["vocab_size"], n + 6).astype(np.int32)
            for n in lengths]
    sm = SlotManager(model, params, max_slots=5, window=4)
    slots = sm.admit([s[:n] for s, n in zip(seqs, lengths)])
    want = [_reference_rows(reference, params, s) for s in seqs]
    for step in range(7):
        got = np.asarray(sm._logits)
        for slot, w, n in zip(slots, want, lengths):
            assert np.abs(got[slot] - w[n - 1 + step]).max() < TOL
        if step == 6:
            break
        # feed the sequence's own next token, not the argmax: plant it as
        # the only finite logit of the slot's row
        forced = np.full(got.shape, -np.inf, np.float32)
        for slot, s, n in zip(slots, seqs, lengths):
            forced[slot, s[n + step]] = 0.0
        sm._logits = jnp.asarray(forced)
        sm.step()
    assert sm.stats["step_traces"] == 1


def test_cache_leaves_have_the_slot_axis_first(lfm2):
    model, _, _ = lfm2
    cache = model.init_cache(5, jnp.float32)
    assert [sorted(c) for c in cache] == [["conv"], ["k", "v"], ["conv"],
                                          ["conv"]]
    assert cache[0]["conv"].shape == (5, 3, 32)
    assert cache[1]["k"].shape == (5, 2, 64, 8)
    assert all(leaf.shape[0] == 5 for leaf in jax.tree_util.tree_leaves(cache))


def test_conv_state_is_taken_at_the_prompt_length():
    """A padded row leaves the state of position ``length``, whatever the
    padding holds; a row shorter than the taps leaves zeros in front."""
    conv = nn.GatedShortConv(8, taps=3)
    p = conv.make_params(jax.random.key(1), None)
    x = jax.random.normal(jax.random.key(2), (2, 16, 8))
    _, state = conv.prefill(p, x, jnp.asarray([2, 11]), jnp.float32)
    for row, n in ((0, 2), (1, 11)):
        _, alone = conv.prefill(p, x[row:row + 1, :n], n, jnp.float32)
        assert np.allclose(state[row], alone[0], atol=1e-6)
    assert np.all(np.asarray(state[0, 0]) == 0.0)
    y_seq = conv.call(p, x[:, :12])
    y, _ = conv.decode_step(p, x[1:2, 11], state[1:2])
    assert np.allclose(y[0], y_seq[1, 11], atol=1e-5)


# (b) the shares of the expert layer add up to the uncut layer -------------
def _expert_tree(e=64, d=16, f=12, seed=3):
    layer = nn.RoutedExperts(d, f, e, 4)
    shapes = jax.eval_shape(lambda k: layer.make_params(k, None),
                            jax.random.key(0))
    return weights.make_params(shapes, seed, {"std": 0.3, "bias_std": 0.1})


def _share(tree, first, count):
    return dict(tree, **{k: tree[k][first:first + count]
                         for k in ("w1", "w3", "w2")})


@pytest.mark.parametrize("first", [0, 16, 32, 48])
def test_a_share_is_the_references_share(first):
    tree = _expert_tree()
    u = jax.random.normal(jax.random.key(7), (40, 16))
    layer = nn.RoutedExperts(16, 12, 64, 4, first=first, count=16)
    got, _ = layer.routed(_share(tree, first, 16), u)
    want = lfm2moe.routed_experts(
        _share(tree, first, 16), u,
        {"num_experts_per_tok": 4, "experts_first": first})
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < TOL


def test_shares_add_up_to_the_uncut_reference_layer():
    tree = _expert_tree()
    u = jax.random.normal(jax.random.key(8), (40, 16))
    total = 0.0
    for first in (0, 16, 32, 48):
        layer = nn.RoutedExperts(16, 12, 64, 4, first=first, count=16)
        total = total + layer.routed(_share(tree, first, 16), u)[0]
    whole = lfm2moe.routed_experts(tree, u, {"num_experts_per_tok": 4})
    assert np.abs(np.asarray(total) - np.asarray(whole)).max() < TOL
    # and the layer run whole is the same sum
    uncut, hit = nn.RoutedExperts(16, 12, 64, 4).routed(tree, u)
    assert np.abs(np.asarray(uncut) - np.asarray(whole)).max() < TOL
    assert 1 <= int(hit) <= 64


# (c) nothing is dropped under a routing skewed onto one expert ------------
@pytest.mark.parametrize("n_tokens", [5, 96, 300])
def test_no_assignment_is_dropped_under_skew(n_tokens):
    """A bias that sends EVERY token's first choice to expert 5 and its
    other three to experts 1, 2, 3: four groups of ``n_tokens`` rows and
    60 empty ones. Every token still gets all four of its experts (the
    capacity dispatch of ``nn.MoE`` would keep 1.25 x 4 N / 64 of them)."""
    tree = _expert_tree()
    bias = np.zeros(64, np.float32)
    bias[[5, 1, 2, 3]] = [8.0, 4.0, 4.0, 4.0]
    tree = dict(tree, expert_bias=jnp.asarray(bias))
    u = jax.random.normal(jax.random.key(9), (n_tokens, 16))
    layer = nn.RoutedExperts(16, 12, 64, 4)
    chosen, w = layer.route(tree, u)
    assert sorted(np.unique(np.asarray(chosen))) == [1, 2, 3, 5]
    assert np.allclose(np.asarray(w).sum(-1), 1.0, atol=1e-5)
    got, hit = layer.routed(tree, u, live=np.ones(n_tokens, bool))
    want = lfm2moe.routed_experts(tree, u, {"num_experts_per_tok": 4})
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < TOL
    assert int(hit) == 4


def test_dead_rows_are_left_out_and_not_counted():
    tree = _expert_tree()
    u = jax.random.normal(jax.random.key(10), (12, 16))
    layer = nn.RoutedExperts(16, 12, 64, 4)
    chosen, _ = layer.route(tree, u)
    live = np.zeros(12, bool)
    live[[2, 7]] = True
    y, hit = layer.routed(tree, u, live=live)
    assert int(hit) == len(np.unique(np.asarray(chosen)[live]))
    # a dead row is left out of the product; a live one is what it was
    whole, _ = layer.routed(tree, u)
    assert np.all(np.asarray(y)[~live] == 0.0)
    assert np.abs(np.asarray(y)[live] - np.asarray(whole)[live]).max() < TOL


# (d) GPT-2's executables are the programs they were ------------------------
def _gpt_pair_before_the_protocol(sm):
    """``SlotManager._build_fns`` as it stood before the protocol, spelled
    against ``.gpt`` and ``{"k", "v"}`` by name (the dense, unlayouted,
    pool-less pair)."""
    model, gpt, stats = sm.model, sm.model.gpt, sm.stats
    top_k, top_p, pmax, n_steps = (sm.top_k, sm.top_p, sm.max_position,
                                   sm.steps_per_sync)

    def prefill(params, cache, logits_buf, ids, prompt_len, slot_idx):
        stats.tick("prefill_traces")
        tmp = gpt.init_cache(ids.shape[0], cache[0]["k"].dtype)
        h_last, tmp = gpt.prefill(params["gpt"], tmp, ids, prompt_len)
        rows = model._lm_logits(params, h_last)
        cache = [{"k": c["k"].at[slot_idx].set(t["k"]),
                  "v": c["v"].at[slot_idx].set(t["v"])}
                 for c, t in zip(cache, tmp)]
        logits_buf = logits_buf.at[slot_idx].set(
            rows.astype(logits_buf.dtype))
        return cache, logits_buf

    def step(params, cache, logits_buf, lengths, active, temps, key):
        stats.tick("step_traces")

        def one(carry, _):
            cache, logits, lengths, key = carry
            tok, key = select_tokens(logits, temps, key, top_k, top_p)
            pos = jnp.minimum(lengths, pmax - 1)
            h, cache = gpt.decode_step(params["gpt"], cache, tok, pos,
                                       in_place=False)
            logits = model._lm_logits(params, h).astype(logits.dtype)
            lengths = lengths + active.astype(lengths.dtype)
            return (cache, logits, lengths, key), tok

        lengths = jnp.asarray(lengths, jnp.int32)
        (cache, logits_buf, _, key), toks = jax.lax.scan(
            one, (cache, logits_buf, lengths, key), None, length=n_steps)
        return cache, logits_buf, key, toks

    return (jax.jit(prefill, donate_argnums=(1, 2)),
            jax.jit(step, donate_argnums=(1, 2, 6)))


@pytest.mark.parametrize("which", ["jit_prefill", "jit_step"])
def test_gpt_executables_lower_to_the_same_text(which, monkeypatch):
    # request tracing off: the slot table then keeps the raw jitted pair
    monkeypatch.setattr(reqtrace, "_trace_on", False)
    model = GPTForCausalLM(vocab_size=61, hidden_size=32, n_layers=2,
                           n_heads=4, max_position=64)
    params = model.setup(jax.random.key(0), None)[0]
    sm = SlotManager(model, params, 4, window=2, top_k=5, top_p=0.9)
    before = _gpt_pair_before_the_protocol(sm)
    if which == "jit_prefill":
        args = (sm.params, sm._cache, sm._logits,
                np.zeros((2, prompt_bucket(9, 64)), np.int32),
                np.ones(2, np.int32), np.array([0, 4], np.int32))
        old, new = before[0], sm._prefill_fn
    else:
        args = (sm.params, sm._cache, sm._logits, sm.lengths, sm.active,
                sm.temps, sm._key)
        old, new = before[1], sm._step_fn
    a, b = old.lower(*args).as_text(), new.lower(*args).as_text()
    # the module is named after the function: the same name on both sides
    assert a.replace("jit_" + old.__name__, which) == b


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
def test_engine_refuses_a_feature_the_model_does_not_carry(lfm2, feature,
                                                           kwargs):
    model, params, _ = lfm2
    with pytest.raises(TypeError, match=f"'{feature}'"):
        ServingEngine(model, params, max_slots=2, **kwargs)


def test_engine_refuses_a_model_without_the_protocol():
    with pytest.raises(TypeError, match="protocol"):
        ServingEngine(nn.Linear(4, 4).build(1), max_slots=2)


# through the engine: tokens, spans and counters ---------------------------
def test_engine_serves_the_model_and_stamps_the_expert_counters(lfm2):
    model, params, reference = lfm2
    obs.default_tracer().clear()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 97, n).astype(np.int32) for n in (2, 5, 17, 1)]
    with ServingEngine(model, params, max_slots=3, max_queue=8) as eng:
        outs = [h.result(timeout=120)
                for h in [eng.submit(p, 10) for p in prompts]]
        stats = dict(eng.stats)
    for p, o in zip(prompts, outs):
        o = np.asarray(o)
        rows = _reference_rows(reference, params, o)[len(p) - 1:len(o) - 1]
        # a served token is the reference's best, or within the tolerance
        assert (rows.max(-1) - rows[np.arange(10), o[len(p):]]).max() < TOL
    # a ``serve/step`` describes the block it dispatches and carries the
    # ``experts_hit`` of the block it reads back, one older
    # (docs/observability.md)
    every = [s.attrs for s in obs.default_tracer().spans()
             if s.name == "serve/step"]
    steps = [a for a in every if "live" in a]
    reads = [a for a in every if "experts_hit" in a]
    fills = [s.attrs for s in obs.default_tracer().spans()
             if s.name == "serve/prefill"]
    assert steps and all(a["experts"] == "ragged_dot" for a in steps)
    assert all(a["assignments"] == 2 * a["live"] for a in steps)
    assert len(reads) == len(steps)              # every block read once
    # the i-th block read is the i-th block dispatched
    assert all(1 <= r["experts_hit"] <= min(8, d["assignments"])
               for d, r in zip(steps, reads))
    assert all(a["assignments"] == 2 * a["tokens"] for a in fills)
    assert stats["moe_assignments"] == sum(
        a["assignments"] for a in steps + fills)
    assert stats["moe_experts_hit"] == pytest.approx(
        sum(a["experts_hit"] for a in reads), rel=1e-5)


def test_prefill_spans_name_the_product_their_rows_take(lfm2):
    """A prompt of 40 tokens fills a prefill of 4 rows x 64 positions x 2
    assignments = 512, the grouped matmul's; one of 5 a prefill of 4 x 16
    x 2 = 128, ``ragged_dot``'s; a step of 4 slots x 2 stays on
    ``ragged_dot``. The spans and ``engine.stats`` say so, and the tokens
    served through either are the reference's."""
    model, params, reference = lfm2
    obs.default_tracer().clear()
    rng = np.random.default_rng(38)
    prompts = [rng.integers(0, 97, n).astype(np.int32) for n in (40, 5)]
    with ServingEngine(model, params, max_slots=4, max_queue=8) as eng:
        outs = [np.asarray(eng.submit(p, 6).result(timeout=120))
                for p in prompts]
        stats = dict(eng.stats)
    for p, o in zip(prompts, outs):
        rows = _reference_rows(reference, params, o)[len(p) - 1:len(o) - 1]
        assert (rows.max(-1) - rows[np.arange(6), o[len(p):]]).max() < TOL
    spans = obs.default_tracer().spans()
    fills = [s.attrs for s in spans if s.name == "serve/prefill"]
    assert [(a["bucket"], a["experts"]) for a in fills] == [
        (64, "gmm"), (16, "ragged_dot")]
    steps = [s.attrs for s in spans
             if s.name == "serve/step" and "live" in s.attrs]
    assert steps and {a["experts"] for a in steps} == {"ragged_dot"}
    assert (stats["moe_prefills_gmm"], stats["moe_prefills_ragged_dot"]) \
        == (1, 1)


def test_a_model_without_experts_stamps_none_of_it():
    obs.default_tracer().clear()
    model = GPTForCausalLM(vocab_size=61, hidden_size=32, n_layers=2,
                           n_heads=4, max_position=64)
    params = model.setup(jax.random.key(0), None)[0]
    with ServingEngine(model, params, max_slots=2) as eng:
        eng.submit(np.arange(5, dtype=np.int32), 4).result(timeout=120)
        assert not [k for k in eng.stats if k.startswith("moe_")]
    for s in obs.default_tracer().spans():
        if s.name in ("serve/step", "serve/prefill"):
            assert not {"experts", "experts_hit", "assignments"} & set(s.attrs)


def test_model_apply_is_the_reference_whole_pass(lfm2):
    model, params, reference = lfm2
    ids = np.random.default_rng(4).integers(0, 97, 64).astype(np.int32)
    got = np.asarray(model.apply(params, (), ids[None])[0])
    assert np.abs(got - _reference_rows(reference, params, ids)).max() < TOL
