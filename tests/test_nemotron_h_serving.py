"""A hybrid of state-space, expert and attention layers through the model
protocol: Nemotron-H at a small size on the CPU (hidden 32, Mamba-2 mixers of
4 heads of 8 over 2 groups of a 16-wide state, prompt chunks of 8, latent
squared-ReLU experts 16 of which 4 are held, 4 a token, beside a shared one,
attention of 4 query heads over 2 K/V heads with no positions, the pattern
``MEM*EM``), seeded random float32 weights, held to
``benchmarks/reference/nemotron3.py`` (which imports nothing of the program
and walks the recurrence a position at a time where the program takes
chunks).

Tolerance: float32 throughout, so the program and the reference differ only
in the ORDER of float32 sums (a chunk's masked quadratic product and the
chunks' scan against one position after the other; a cached step against a
whole pass): at most 1.4e-5 on logits that spread by about 1. 5e-5 leaves
three times that and passes neither planted fault nor a state that is not
carried: each moves a logit by whole tenths or units."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from benchmarks.harness import weights
from benchmarks.reference import nemotron3 as reference_mod
from bigdl_tpu import obs
from bigdl_tpu.models.gpt import prompt_bucket
from bigdl_tpu.models.nemotron_h import NemotronHForCausalLM
from bigdl_tpu.serving import ServingEngine
from bigdl_tpu.serving.slots import SlotManager

KW = dict(vocab_size=61, hidden_size=32, hybrid_override_pattern="MEM*EM",
          mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
          conv_kernel=4, chunk_size=8, num_attention_heads=4,
          num_key_value_heads=2, head_dim=8, n_routed_experts=16,
          num_experts_per_tok=4, n_shared_experts=1, moe_intermediate_size=12,
          moe_latent_size=16, moe_shared_expert_intermediate_size=24,
          norm_topk_prob=True, routed_scaling_factor=5.0,
          layer_norm_epsilon=1e-5, max_position=64, experts_first=4,
          experts_held=4, prefill_block=16)
TOL = 5e-5
PMAX, VOCAB = KW["max_position"], KW["vocab_size"]
SPEC = {"std": 0.2, "gain_std": 0.1, "bias_std": 0.5}
# the benchmark's own draw at these widths: every rank-1 leaf but a gain
# near 0, so A ~ -1 and dt ~ softplus(u W_dt) (a state that forgets within a
# few positions)
CELL_SPEC = {"std": 0.2, "gain_std": 0.1, "bias_std": 0.01}
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = "family", "draw"


def _family(params, seed=0):
    """The Nemotron-H family's initialisation of every Mamba layer's
    ``A_log``, ``dt_bias`` and ``D`` (``chip_smoke.family_state_init``)."""
    import chip_smoke
    return chip_smoke.family_state_init(params, seed)


def _params(model, spec=SPEC, seed=3):
    shapes = jax.eval_shape(lambda k: model.setup(k, None)[0],
                            jax.random.key(0))
    return weights.make_params(shapes, seed, spec)


@pytest.fixture(scope="module")
def hybrid():
    model = NemotronHForCausalLM(**KW)
    params = _params(model)
    reference, controls = reference_mod.make({
        "constructor_kwargs": KW,
        "controls": ["operands:bfloat16", "operands:float8_e4m3fn"],
        "faults": list(reference_mod.FAULTS)})
    return model, params, reference, controls


def _reference_rows(reference, params, seq):
    ids = np.zeros(PMAX, np.int32)
    ids[:len(seq)] = seq
    return np.asarray(reference(params, ids, np.arange(PMAX, dtype=np.int32)))


# (a) the chunked prompt pass is the recurrence ----------------------------
MIXER = dict(hidden_size=32, num_heads=4, head_dim=8, n_groups=2,
             state_size=16, conv_kernel=4, chunk_size=128)
MIXER_KW = dict(mamba_num_heads=4, mamba_head_dim=8, n_groups=2,
                ssm_state_size=16, conv_kernel=4, layer_norm_epsilon=1e-5)


def _mixer(init):
    mixer = nn.Mamba2Mixer(**MIXER)
    shapes = jax.eval_shape(lambda k: mixer.make_params(k, None),
                            jax.random.key(0))
    p = weights.make_params(shapes, 5, CELL_SPEC)
    if init == "family":
        p = _family({"layers": [{"mixer": p}]}, 5)["layers"][0]["mixer"]
    return mixer, p


def _recurrence(p, u):
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference_mod.mamba(
            p, u, MIXER_KW, lambda a, b: a @ b.astype(jnp.float32),
            lambda v: v))


@pytest.mark.parametrize("init", FAMILY)
@pytest.mark.parametrize("length", [1, 127, 128, 129, 300])
def test_the_chunked_pass_is_the_recurrence(init, length):
    """Chunks of 128 (the published ``chunk_size``): one position, a chunk
    short of whole, whole, one past, and two chunks and a ragged third,
    under the benchmark's draw (the state forgets within a few positions)
    and under the family's initialisation (it remembers hundreds)."""
    mixer, p = _mixer(init)
    u = jax.random.normal(jax.random.key(length), (1, length, 32))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(mixer.call(p, u))[0]
    want = _recurrence(p, u[0])
    assert np.abs(got - want).max() < TOL * max(1.0, np.abs(want).max())


def test_dropping_the_carry_between_chunks_fails_under_the_family_init():
    """The chunks scanned one by one, the state handed on: the one-call
    pass. Each started from zero instead: under the family's long memory
    the outputs move through the whole of the next chunk, its last
    position by far more than the tolerance; under the benchmark's draw
    only a chunk's first few positions move and its last does not
    (PERF.md section 7: the chip's ``correct``, on that draw, sees a
    state's first few positions only; this test sees the rest)."""
    moved = {}
    for init in FAMILY:
        mixer, p = _mixer(init)
        u = jax.random.normal(jax.random.key(7), (1, 300, 32))
        with jax.default_matmul_precision("highest"):
            z, xbc, dt = mixer._project(p, u, jnp.float32)
            window = jnp.pad(xbc, ((0, 0), (3, 0), (0, 0)))
            x, b, c, dt = mixer._split(p, mixer._conv(p, window), dt)
            a = mixer._a(p)
            whole, _ = mixer.scan_chunks(x, dt, a, b, c)
            parts, carried, state = [], [], None
            for lo in range(0, 300, 128):
                at = slice(lo, lo + 128)
                y, _ = mixer.scan_chunks(x[:, at], dt[:, at], a, b[:, at],
                                         c[:, at])
                parts.append(y)
                y, state = mixer.scan_chunks(x[:, at], dt[:, at], a,
                                             b[:, at], c[:, at], state)
                carried.append(y)
        scale = float(jnp.abs(whole).max())
        assert float(jnp.abs(jnp.concatenate(carried, 1) - whole).max()) \
            < TOL * scale
        gap = jnp.abs(jnp.concatenate(parts, 1) - whole)
        moved[init] = (float(gap[:, 128].max()) / scale,
                       float(gap[:, 160].max()) / scale,
                       float(gap[:, 255].max()) / scale)
    assert min(moved["family"]) > 20 * TOL
    assert moved["draw"][0] > 1000 * TOL and moved["draw"][1] < TOL


def test_the_weight_draw_forgets_within_a_few_positions():
    """``assumed.weights`` of the configuration: under the benchmark's draw
    at the cell's widths (every matrix N(0, 0.02), ``A_log`` and ``dt_bias``
    N(0, 0.01)) a head's mean decay a position, ``exp(dt A)`` over
    normed rows, is about a half (a position's state is an e-th of itself
    1.4 positions on); under the family's initialisation a tenth of the
    heads keep 99 % a position and more (a hundred positions and more)."""
    rng = np.random.default_rng(0)
    u = rng.standard_normal((512, 4096)).astype(np.float32)
    u /= np.sqrt(np.mean(u ** 2, -1, keepdims=True))
    w_dt = 0.02 * rng.standard_normal((4096, 128)).astype(np.float32)
    bias = 0.01 * rng.standard_normal(128)
    a = -np.exp(0.01 * rng.standard_normal(128))
    dt = np.logaddexp(0.0, u @ w_dt + bias)
    drawn = np.exp(dt * a).mean(0)
    assert 0.45 < drawn.mean() < 0.55 and drawn.max() < 0.6
    step = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), 128))
    family = np.exp(-step * rng.uniform(1, 16, 128))
    assert family.mean() > 0.8 and (family > 0.99).mean() > 0.1


# (b) the whole pass, the controls and the faults --------------------------
@pytest.mark.parametrize("length", [1, 5, 8, 9, 17, 40, 64])
def test_apply_is_the_references_whole_pass(hybrid, length):
    model, params, reference, _ = hybrid
    ids = np.random.default_rng(length).integers(0, VOCAB, length)
    got = np.asarray(model.apply(params, (), jnp.asarray(ids)[None])[0])
    want = _reference_rows(reference, params, ids)[:length]
    assert np.abs(got - want).max() < TOL


def _gaps(reference_rows, choices, n):
    rows = reference_rows[:n]
    return rows.max(-1) - rows[np.arange(n), np.asarray(choices)[:n]]


@pytest.mark.parametrize("spec", ["wide", "cell"])
@pytest.mark.parametrize("fault", reference_mod.FAULTS)
def test_a_planted_fault_fails_at_the_tolerance(hybrid, fault, spec):
    """Under a wide draw and under the benchmark's short-memory one: a
    state that does not carry and routed experts without their scale each
    put a token first that lies whole tenths under the reference's best."""
    model, params, reference, controls = hybrid
    if spec == "cell":
        params = _params(model, CELL_SPEC)
    ids = np.random.default_rng(9).integers(0, VOCAB, 60).astype(np.int32)
    rows = _reference_rows(reference, params, ids)
    wrong = controls["fault:" + fault](
        params, np.pad(ids, (0, PMAX - 60)), np.arange(60)).argmax(-1)
    assert _gaps(rows, wrong, 60).max() > 1000 * TOL


def test_the_float8_control_fails_and_bfloat16_reads_between(hybrid):
    _, params, reference, controls = hybrid
    ids = np.random.default_rng(10).integers(0, VOCAB, 60).astype(np.int32)
    rows = _reference_rows(reference, params, ids)
    seq = np.pad(ids, (0, PMAX - 60))
    pick = {name: _gaps(rows, controls["operands:" + name](
        params, seq, np.arange(60)).argmax(-1), 60)
        for name in ("bfloat16", "float8_e4m3fn")}
    assert pick["float8_e4m3fn"].max() > 1000 * TOL
    assert np.square(pick["bfloat16"]).mean() \
        < np.square(pick["float8_e4m3fn"]).mean()


# (c) prefill then decoding through the slot table against the whole pass --
def _drive(sm, slots, seqs, lengths, want, steps):
    for step in range(steps + 1):
        got = np.asarray(sm._logits)
        for slot, w, n in zip(slots, want, lengths):
            assert np.abs(got[slot] - w[n - 1 + step]).max() < TOL, (step, n)
        if step == steps:
            break
        # feed the sequence's own next token, not the argmax: plant it as
        # the only finite logit of the slot's row
        forced = np.full(got.shape, -np.inf, np.float32)
        for slot, s, n in zip(slots, seqs, lengths):
            forced[slot, s[n + step]] = 0.0
        sm._logits = jnp.asarray(forced)
        sm.step()


@pytest.mark.parametrize("init", FAMILY)
@pytest.mark.parametrize("lengths", [(1, 5), (7, 8, 9), (4, 13, 24, 30),
                                     (5, 40)])
def test_prefill_then_steps_agree_with_reference(hybrid, lengths, init):
    """Rows of different lengths right-padded to one bucket (16, 32 or 64
    positions, chunks of 8, prompt blocks of 16): shorter than the
    convolution, inside a chunk, on a chunk's edge, several chunks, rows
    that end a block or more before the longest (their state carried
    through the blocks they do not fill), a bucket whose last block is
    not walked; every slot's logits after the prefill and after each
    step, to the table's last row, are the reference's at that position.
    The state as of each row's own length is what the steps continue
    from."""
    model, params, reference, _ = hybrid
    if init == "family":
        params = _family(params)
    rng = np.random.default_rng(sum(lengths))
    steps = PMAX - max(lengths) - 1
    seqs = [rng.integers(0, VOCAB, PMAX).astype(np.int32) for _ in lengths]
    sm = SlotManager(model, params, max_slots=5, window=4)
    slots = sm.admit([s[:n] for s, n in zip(seqs, lengths)])
    want = [_reference_rows(reference, params, s) for s in seqs]
    _drive(sm, slots, seqs, lengths, want, steps)
    assert sm.stats["step_traces"] == 1 and sm.kv_write == "scatter"


def test_the_step_built_with_the_kernel_gives_the_same_logits(
        hybrid, monkeypatch):
    """``ops/ssm_step.py`` taken where ``applies`` says yes of the state
    as allocated (overridden here as on the chip, the kernel
    interpreted): two live slots beside a free one, every logit the
    reference's, the free slot's state never moved."""
    from bigdl_tpu.ops import ssm_step
    monkeypatch.setattr(ssm_step, "applies", lambda s, layout=None: True)
    model, params, reference, _ = hybrid
    params = _family(params)
    rng = np.random.default_rng(11)
    lengths = (3, 21)
    seqs = [rng.integers(0, VOCAB, PMAX).astype(np.int32) for _ in lengths]
    sm = SlotManager(model, params, max_slots=3, window=2)
    slots = sm.admit([s[:n] for s, n in zip(seqs, lengths)])
    free = ({0, 1, 2} - set(slots)).pop()
    before = [np.asarray(c["ssm"][free]) for c in sm._cache if "ssm" in c]
    want = [_reference_rows(reference, params, s) for s in seqs]
    _drive(sm, slots, seqs, lengths, want, 14)
    after = [np.asarray(c["ssm"][free]) for c in sm._cache if "ssm" in c]
    assert all((a == b).all() for a, b in zip(after, before))
    text = sm._step_fn.lower(sm.params, sm._cache, sm._logits, sm.lengths,
                             sm.active, sm.temps, sm._key).as_text()
    assert "_ssm_update" in text


def test_a_reused_slot_reads_nothing_of_its_former_occupant(hybrid):
    """A stream retired at position 42 leaves its S, its taps and 42 rows
    of K and V behind. The freed slot is poisoned with NaN in every leaf
    (one NaN carried, convolved or read would make every logit NaN); a
    3-token prompt takes the slot and decodes beside a stream that was
    live all along."""
    model, params, reference, _ = hybrid
    params = _family(params)
    rng = np.random.default_rng(3)
    old, stays, new = (rng.integers(0, VOCAB, PMAX).astype(np.int32)
                       for _ in range(3))
    sm = SlotManager(model, params, max_slots=2, window=2)
    gone, kept = sm.admit([old[:30], stays[:9]])

    def force_and_step(pairs):
        forced = np.full((2, VOCAB), -np.inf, np.float32)
        for slot, tok in pairs:
            forced[slot, tok] = 0.0
        sm._logits = jnp.asarray(forced)
        sm.step()

    for i in range(12):
        force_and_step([(gone, old[30 + i]), (kept, stays[9 + i])])
    sm.retire(gone)
    sm._cache = jax.tree_util.tree_map(
        lambda leaf: leaf.at[gone].set(jnp.nan), sm._cache)
    assert sm.admit([new[:3]]) == [gone]
    want_new = _reference_rows(reference, params, new)
    want_kept = _reference_rows(reference, params, stays)
    for i in range(25):
        got = np.asarray(sm._logits)
        assert np.isfinite(got).all()
        assert np.abs(got[gone] - want_new[2 + i]).max() < TOL
        assert np.abs(got[kept] - want_kept[20 + i]).max() < TOL
        force_and_step([(gone, new[3 + i]), (kept, stays[21 + i])])


def test_engine_serves_streams_admitted_at_different_times(hybrid):
    """Five prompts through two slots: later ones are admitted as earlier
    ones retire, and every served token is the reference's best (or
    within the tolerance of it) at its position of the whole sequence."""
    model, params, reference, _ = hybrid
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, VOCAB, n).astype(np.int32)
               for n in (3, 20, 11, 8, 29)]
    with ServingEngine(model, params, max_slots=2, max_queue=8,
                       prefill_window=1) as eng:
        outs = [h.result(timeout=300)
                for h in [eng.submit(p, 30) for p in prompts]]
    for p, o in zip(prompts, outs):
        o = np.asarray(o)
        assert len(o) == len(p) + 30
        rows = _reference_rows(reference, params, o)[len(p) - 1:len(o) - 1]
        assert (rows.max(-1) - rows[np.arange(30), o[len(p):]]).max() < TOL


# (d) the shares of a latent expert layer add up to the whole --------------
def test_eight_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """Section 4 of the model-configs guide: the parts that the eight
    holders of 2 of 16 latent squared-ReLU experts give (each through the
    up-projection that all of them share), with the shared expert (which
    every holder computes alike) counted once, are the uncut reference's
    layer."""
    d, f, e, k, lat, fs = 32, 12, 16, 4, 16, 24
    whole = nn.SharedAndRoutedExperts(d, f, e, k, shared_size=fs,
                                      act="relu2", latent_size=lat,
                                      scaling=5.0)
    shapes = jax.eval_shape(lambda key: whole.make_params(key, None),
                            jax.random.key(0))
    params = weights.make_params(shapes, 7, SPEC)
    x = jax.random.normal(jax.random.key(1), (23, d))
    total = whole.shared.call(params["shared"], x)
    held_sum = 0
    for share in range(8):
        part = nn.RoutedExperts(d, f, e, k, first=2 * share, count=2,
                                act="relu2", latent_size=lat, scaling=5.0)
        mine = {n: v[2 * share:2 * share + 2] if n in ("w1", "w2") else v
                for n, v in params["routed"].items()}
        y, sizes = part.routed_sizes(mine, x)
        assert sizes.shape == (2,)
        held_sum += int(sizes.sum())
        total = total + y
    assert held_sum == 23 * k                 # every assignment lands once
    kw = dict(num_experts_per_tok=k, experts_first=0,
              routed_scaling_factor=5.0)
    with jax.default_matmul_precision("highest"):
        want = reference_mod.experts(
            params, x, kw, lambda a, b: a @ b.astype(jnp.float32))
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < TOL
    y, hit, held = whole.routed(params, x)
    assert np.abs(np.asarray(y) - np.asarray(want)).max() < TOL
    assert int(held) == 23 * k and int(hit) <= e


# (e) the pattern and the configuration's cut ------------------------------
def test_the_published_pattern_and_the_configurations_first_period():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron3-super-ep8-serve.json")) as f:
        config = json.load(f)
    pattern = config["published"]["hybrid_override_pattern"]
    assert len(pattern) == config["published"]["num_hidden_layers"] == 88
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) \
        == (40, 40, 8)
    kw = config["constructor_kwargs"]
    assert kw["hybrid_override_pattern"] == pattern[:11] == "MEMEMEM*EME"
    model = NemotronHForCausalLM(**kw)
    assert "".join(l.kind for l in model.layers) == pattern[:11]
    assert (model.mamba_layers, model.experts_per_token) == (5, 22)
    (table,) = model.cache_tables()
    assert table.leaves == ("k", "v") and table.rows == 6144


def test_the_cells_prompts_take_the_buckets_its_warm_up_builds():
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           "nemotron3-reason-closed.json")) as f:
        traffic = json.load(f)
    pmax = 6144
    lo, hi = (traffic["prompt_tokens"][k] for k in ("min", "max"))
    assert hi + traffic["output_tokens"]["max"] <= pmax
    buckets = {prompt_bucket(n, pmax) for n in range(lo, hi + 1, 7)}
    assert buckets == {128, 256, 512, 1024, 2048, 4096}
    assert all(max(16, 1 << (n - 1).bit_length()) == prompt_bucket(n, pmax)
               for n in range(lo, hi + 1, 97))


# (f) the spans carry the host's own arithmetic ----------------------------
def test_step_and_prefill_spans_carry_the_state_the_host_reckons(hybrid):
    model, params, _, _ = hybrid
    sm = SlotManager(model, params, max_slots=4, window=2)
    sm.admit([np.zeros(n, np.int32) for n in (5, 21)])
    s_bytes = 4 * 4 * 8 * 16                  # one slot's S of one layer
    assert sm.prefill_attrs == {
        "experts": "ragged_dot", "assignments": 4 * 26,
        "ssm_chunks": 1 + 3, "ssm_positions": 26}
    sums = {"ssm_chunks": 4, "ssm_positions": 26, "ssm_slots": 0,
            "ssm_state_bytes": 0, "attn_rows": 0}
    for step in range(12):
        if step == 5:
            sm.admit([np.zeros(17, np.int32)])
            sums["ssm_chunks"] += 3
            sums["ssm_positions"] += 17
        if step == 9:
            sm.retire(0)
        pos = sm.lengths[sm.active].astype(int)
        want = {"ssm_slots": len(pos),
                "ssm_state_bytes": 2 * len(pos) * 3 * s_bytes,
                "attn_rows": int(sum(p + 1 for p in pos))}
        sm.step()
        attrs = dict(sm.step_attrs)
        assert {k: attrs[k] for k in want} == want
        assert 0 <= attrs["experts_hit"] <= 4
        for name, n in want.items():
            sums[name] += n
    assert {k: sm.stats[k] for k in sums} == sums


def test_engine_stamps_the_state_on_its_spans_and_sums_them(hybrid):
    model, params, _, _ = hybrid
    obs.default_tracer().clear()
    rng = np.random.default_rng(4)
    with ServingEngine(model, params, max_slots=2, max_queue=8) as eng:
        for h in [eng.submit(rng.integers(0, VOCAB, n).astype(np.int32), 20)
                  for n in (6, 33, 12)]:
            h.result(timeout=300)
        stats = dict(eng.stats)
    spans = obs.default_tracer().spans()
    steps = [s.attrs for s in spans if s.name == "serve/step"
             and "live" in s.attrs]
    fills = [s.attrs for s in spans if s.name == "serve/prefill"]
    assert steps and fills
    for name in ("ssm_slots", "ssm_state_bytes", "attn_rows"):
        assert stats[name] == sum(a[name] for a in steps)
    for name in ("ssm_chunks", "ssm_positions"):
        assert stats[name] == sum(a[name] for a in fills)
    assert stats["ssm_positions"] == 6 + 33 + 12
    assert all(a["ssm_slots"] == a["live"] for a in steps)
    reads = [s.attrs for s in spans if s.name == "serve/step"
             and "experts_hit" in s.attrs]
    assert len(reads) == len(steps)
    assert all("assignments_held" in a for a in reads)


# (g) the engine refuses what the model does not carry, by name ------------
@pytest.mark.parametrize("feature, kwargs", [
    ("paged", dict(paged=True)),
    ("spec_tokens", dict(spec_tokens=4)),
    ("lora", dict(lora=True)),
    ("int8_weights", dict(int8_weights=True)),
    ("int8_kv", dict(int8_kv=True)),
    ("tp", dict(tp=2)),
    ("kv_snapshot", dict(kv_snapshot=True, snapshot_dir="unused")),
])
def test_engine_refuses_a_feature_the_model_does_not_carry(hybrid, feature,
                                                           kwargs):
    model, params, _, _ = hybrid
    with pytest.raises(TypeError, match=f"'{feature}'"):
        ServingEngine(model, params, max_slots=2, **kwargs)


def test_an_unknown_layer_character_is_refused():
    with pytest.raises(ValueError, match="layer character"):
        NemotronHForCausalLM(**dict(KW, hybrid_override_pattern="ME-"))
    with pytest.raises(ValueError, match="expert form"):
        nn.RoutedExperts(8, 4, 4, 2, act="gelu")
