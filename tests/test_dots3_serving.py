"""A table read by selection, a ring and a share of the experts through the
model protocol: dots3-note at a small size on the CPU (hidden 64, 4 + 2
heads, ranks 16/16/24, an indexer of 3 heads picking 8 positions, a window
of 5, 16 experts of which 4 are held, 4 a token, 5 layers in the published
pattern, prompt blocks of 8 queries), seeded random float32 weights, held to
``benchmarks/reference/dots3.py`` (which imports nothing of the program and
EXPANDS the latents the program never expands).

Tolerance: float32 throughout, so the program and the reference differ only
in the ORDER of float32 sums (``W_kvb`` folded into the query against keys
expanded a head; a cached step against a whole pass): at most 1e-5 on logits
that spread by 1.6. 5e-5 leaves five times that and would not pass a wrong
row, selection or window: every planted fault moves a logit by whole units.
No index score of these weights ties at a selection's edge within that."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from benchmarks.harness import weights
from benchmarks.reference import dots3 as reference_mod
from bigdl_tpu import obs
from bigdl_tpu.models.dots3 import Dots3ForCausalLM
from bigdl_tpu.models.gpt import prompt_bucket
from bigdl_tpu.serving import ServingEngine
from bigdl_tpu.serving import slots as slots_mod
from bigdl_tpu.serving.protocol import RowTable, positions_table
from bigdl_tpu.serving.slots import SlotManager

TOL = 5e-5
KW = dict(vocab_size=50, hidden_size=64, intermediate_size=96,
          moe_intermediate_size=24,
          layer_types=["full_attention", "full_attention",
                       "sliding_attention", "sliding_attention",
                       "sliding_attention"],
          first_k_dense_replace=1, n_routed_experts=16, n_shared_experts=1,
          num_experts_per_tok=4, num_attention_heads=4, q_lora_rank=16,
          kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
          v_head_dim=16, rope_theta=8e7, index_n_heads=3, index_head_dim=16,
          index_topk=8, swa_num_attention_heads=2, swa_q_lora_rank=16,
          swa_kv_lora_rank=24, swa_qk_nope_head_dim=24,
          swa_qk_rope_head_dim=8, swa_v_head_dim=16, swa_rope_theta=5e4,
          sliding_window_size=5, max_position=64, experts_first=4,
          experts_held=4, prefill_block=8)
K, WIN, PMAX, VOCAB = (KW["index_topk"], KW["sliding_window_size"],
                       KW["max_position"], KW["vocab_size"])
SPEC = {"std": 0.2, "gain_std": 0.1, "bias_std": 0.5}
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def dots():
    model = Dots3ForCausalLM(**KW)
    shapes = jax.eval_shape(lambda k: model.setup(k, None)[0],
                            jax.random.key(0))
    params = weights.make_params(shapes, 3, SPEC)
    reference, controls = reference_mod.make({
        "constructor_kwargs": KW,
        "controls": ["operands:bfloat16", "operands:float8_e4m3fn"],
        "faults": list(reference_mod.FAULTS)})
    return model, params, reference, controls


def _reference_rows(reference, params, seq):
    ids = np.zeros(PMAX, np.int32)
    ids[:len(seq)] = seq
    return np.asarray(reference(params, ids, np.arange(PMAX, dtype=np.int32)))


# (a) the whole pass against the reference ---------------------------------
@pytest.mark.parametrize("length", [1, 5, 8, 9, 17, 40, 64])
def test_apply_is_the_references_whole_pass(dots, length):
    """Lengths inside the window and the selection, on the selection's edge
    (8 positions: all read; 9: one left out), past both, and no multiple
    of the prompt block."""
    model, params, reference, _ = dots
    ids = np.random.default_rng(length).integers(0, VOCAB, length).astype(
        np.int32)
    got = np.asarray(model.apply(params, (), ids[None])[0])
    want = _reference_rows(reference, params, ids)[:length]
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL


def test_two_rows_of_a_batch_are_two_sequences(dots):
    model, params, reference, _ = dots
    ids = np.random.default_rng(2).integers(0, VOCAB, (2, 37)).astype(np.int32)
    got = np.asarray(model.apply(params, (), ids)[0]).reshape(2, 37, -1)
    for row in range(2):
        want = _reference_rows(reference, params, ids[row])[:37]
        assert np.abs(got[row] - want).max() < TOL


# (d) the controls and the planted faults against the same comparison ------
def _gaps(reference_rows, choices, n):
    rows = reference_rows[:n]
    return rows.max(-1) - rows[np.arange(n), np.asarray(choices)[:n]]


@pytest.mark.parametrize("fault", reference_mod.FAULTS)
def test_a_planted_fault_fails_at_the_tolerance(dots, fault):
    """The token a faulty reference puts first lies whole units under the
    true reference's best somewhere past the selection's and the window's
    reach, and nowhere before it."""
    _, params, reference, controls = dots
    ids = np.random.default_rng(9).integers(0, VOCAB, 60).astype(np.int32)
    rows = _reference_rows(reference, params, ids)
    wrong = controls["fault:" + fault](
        params, np.pad(ids, (0, PMAX - 60)), np.arange(60)).argmax(-1)
    gap = _gaps(rows, wrong, 60)
    # nothing of the mechanism is in play while every position is read
    assert gap[:min(K, WIN)].max() < TOL
    assert gap.max() > 1000 * TOL


def test_the_float8_control_fails_and_bfloat16_reads_between(dots):
    _, params, reference, controls = dots
    ids = np.random.default_rng(10).integers(0, VOCAB, 60).astype(np.int32)
    rows = _reference_rows(reference, params, ids)
    seq = np.pad(ids, (0, PMAX - 60))
    pick = {name: _gaps(rows, controls["operands:" + name](
        params, seq, np.arange(60)).argmax(-1), 60)
        for name in ("bfloat16", "float8_e4m3fn")}
    assert pick["float8_e4m3fn"].max() > 1000 * TOL
    assert pick["bfloat16"].max() < pick["float8_e4m3fn"].max()


# (b) prefill then decoding through the slot table against the whole pass --
@pytest.mark.parametrize("lengths", [(1, 5), (7, 8, 9), (4, 13, 24, 30)])
def test_prefill_then_steps_agree_with_reference(dots, lengths):
    """Rows padded to their bucket (16 or 32 positions, walked 8 at a
    time, the blocks past the longest prompt not at all), prompts shorter
    and longer than the selection and the window, on the selection's edge:
    every slot's logits after the prefill and after each step (which cross
    the selection's edge, wrap the ring several times and run to the
    table's last row) are the reference's at that position."""
    model, params, reference, _ = dots
    rng = np.random.default_rng(sum(lengths))
    steps = PMAX - max(lengths) - 1
    seqs = [rng.integers(0, VOCAB, PMAX).astype(np.int32) for _ in lengths]
    sm = SlotManager(model, params, max_slots=5, window=4)
    slots = sm.admit([s[:n] for s, n in zip(seqs, lengths)])
    want = [_reference_rows(reference, params, s) for s in seqs]
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
    assert sm.stats["step_traces"] == 1


def test_engine_serves_streams_admitted_at_different_times(dots):
    """Five prompts through two slots: later ones are admitted as earlier
    ones retire, and every served token is the reference's best (or
    within the tolerance of it) at its position of the whole sequence."""
    model, params, reference, _ = dots
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, VOCAB, n).astype(np.int32)
               for n in (3, 20, 11, 8, 29)]
    with ServingEngine(model, params, max_slots=2, max_queue=8,
                       prefill_window=1) as eng:
        outs = [h.result(timeout=300)
                for h in [eng.submit(p, 30) for p in prompts]]
        assert eng.slots.kv_write == "scatter"
        assert eng.slots.attn_read == "masked"
    for p, o in zip(prompts, outs):
        o = np.asarray(o)
        assert len(o) == len(p) + 30
        rows = _reference_rows(reference, params, o)[len(p) - 1:len(o) - 1]
        assert (rows.max(-1) - rows[np.arange(30), o[len(p):]]).max() < TOL


def test_the_step_built_with_the_kernel_gives_the_same_logits(
        dots, monkeypatch):
    """``ops/latent_attention.py`` taken where ``applies`` says yes of the
    table as allocated (overridden here as on the chip, the kernel
    interpreted): both kinds of layer read through it, a free slot beside
    the live ones, and every logit is the reference's."""
    from bigdl_tpu.ops import latent_attention
    monkeypatch.setattr(latent_attention, "applies", lambda t, l=None: True)
    model, params, reference, _ = dots
    rng = np.random.default_rng(11)
    lengths = (3, 21)
    seqs = [rng.integers(0, VOCAB, PMAX).astype(np.int32) for _ in lengths]
    sm = SlotManager(model, params, max_slots=3, window=2)
    slots = sm.admit([s[:n] for s, n in zip(seqs, lengths)])
    want = [_reference_rows(reference, params, s) for s in seqs]
    for step in range(14):
        got = np.asarray(sm._logits)
        for slot, w, n in zip(slots, want, lengths):
            assert np.abs(got[slot] - w[n - 1 + step]).max() < TOL, (step, n)
        forced = np.full(got.shape, -np.inf, np.float32)
        for slot, s, n in zip(slots, seqs, lengths):
            forced[slot, s[n + step]] = 0.0
        sm._logits = jnp.asarray(forced)
        sm.step()
    text = sm._step_fn.lower(sm.params, sm._cache, sm._logits, sm.lengths,
                             sm.active, sm.temps, sm._key).as_text()
    assert "latent_attention" in text


# (c) a reused slot reads nothing of its former occupant --------------------
def test_a_reused_slot_reads_nothing_of_its_former_occupant(dots):
    """A stream retired at position 42 leaves 42 latents, 42 index keys
    and a full ring behind. The freed rows are poisoned with NaN (one NaN
    scored, chosen or read would make every logit NaN); a 3-token prompt
    takes the slot and decodes past the selection's edge beside a stream
    that was live all along."""
    model, params, reference, _ = dots
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


# (e) the shares of an expert layer add up to the whole ---------------------
def test_eight_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """Section 4 of the model-configs guide: the parts that the eight
    holders of 2 of 16 experts give, with the shared expert (which every
    holder computes alike) counted once, are the uncut reference's layer."""
    d, f, e, k = 64, 24, 16, 4
    whole = nn.SharedAndRoutedExperts(d, f, e, k)
    shapes = jax.eval_shape(lambda key: whole.make_params(key, None),
                            jax.random.key(0))
    params = weights.make_params(shapes, 7, SPEC)
    x = jax.random.normal(jax.random.key(1), (23, d))
    total = whole.shared.call(params["shared"], x)
    held_sum = 0
    for share in range(8):
        part = nn.RoutedExperts(d, f, e, k, first=2 * share, count=2)
        mine = {n: v[2 * share:2 * share + 2] if n in ("w1", "w3", "w2")
                else v for n, v in params["routed"].items()}
        y, sizes = part.routed_sizes(mine, x)
        assert sizes.shape == (2,)
        held_sum += int(sizes.sum())
        total = total + y
    assert held_sum == 23 * k                 # every assignment lands once
    kw = dict(num_experts_per_tok=k, experts_first=0)
    with jax.default_matmul_precision("highest"):
        def mm(a, b):
            return a @ b.astype(jnp.float32)
        s = params["shared"]
        want = reference_mod.routed_experts(
            params["routed"], x, kw, mm, lambda v: v) \
            + mm(jax.nn.silu(mm(x, s["w1"])) * mm(x, s["w3"]), s["w2"])
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < TOL
    # and the composed layer is one share plus the shared part
    y, hit, held = whole.routed(params, x)
    assert np.abs(np.asarray(y) - np.asarray(want)).max() < TOL
    assert int(held) == 23 * k and int(hit) <= e


# (f) the tables, as the model describes them -------------------------------
def test_the_model_describes_its_three_tables(dots):
    model, _, _, _ = dots
    keys, latents, ring = model.cache_tables()
    assert (keys.leaves, keys.rows, keys.row_axis) == (("kidx",), PMAX, 1)
    assert (latents.leaves, latents.rows, latents.row_axis) == (
        ("ckv",), PMAX, 1)
    assert (ring.leaves, ring.rows, ring.row_axis) == (("win",), 128, 1)
    assert latents.selected and not keys.selected and not ring.selected
    pos = np.arange(PMAX)
    assert (keys.write_row(pos) == pos).all()
    assert (keys.read_rows(pos) == pos + 1).all()
    assert (latents.write_row(pos) == pos).all()
    # off the chip the model's own kernel takes neither of its two tables
    assert keys.own_read is None
    assert latents.own_read(np.zeros((3, PMAX, 128), np.float32)) is None
    assert ring.own_read(np.zeros((3, 128, 128), np.float32)) is None
    assert (latents.read_rows(pos) == np.minimum(pos + 1, K)).all()
    assert (ring.write_row(pos) == pos % WIN).all()
    assert (ring.read_rows(pos) == np.minimum(pos + 1, WIN)).all()
    # the same arithmetic on traced positions
    traced = jax.jit(lambda p: (latents.read_rows(p), ring.read_rows(p)))(pos)
    assert (np.asarray(traced[0]) == np.minimum(pos + 1, K)).all()
    assert (np.asarray(traced[1]) == np.minimum(pos + 1, WIN)).all()
    cache = model.init_cache(3, jnp.float32)
    assert [sorted(c) for c in cache] == [["ckv", "kidx"]] * 2 + [["win"]] * 3
    # a row is kept in whole lanes of 128, zeros behind its 24 or 32 numbers
    assert cache[0]["ckv"].shape == (3, PMAX, 128)
    assert cache[0]["kidx"].shape == (3, PMAX, 16)
    assert cache[2]["win"].shape == (3, 128, 128)


class _OneTable:
    """A model of ONE table shaped as the two kernels know it, whose
    description alone differs."""

    def __init__(self, model, table):
        self._model, self._table = model, table

    def __getattr__(self, name):
        return getattr(self._model, name)

    def cache_tables(self):
        return (self._table,)


@pytest.mark.parametrize("selected", [False, True])
def test_a_table_read_by_selection_is_handed_to_neither_kernel(
        selected, monkeypatch):
    """With both kernels saying they apply (as on the chip) and a table
    laid out as they need it, the table's own word that its rows read are
    CHOSEN keeps the plain write and the masked read; the same table read
    from row 0 on takes both."""
    from bigdl_tpu.models.gpt import GPTForCausalLM
    monkeypatch.setattr(slots_mod, "in_place_applies", lambda *a: True)
    monkeypatch.setattr(slots_mod.decode_attention, "applies",
                        lambda *a: True)
    gpt = GPTForCausalLM(vocab_size=61, hidden_size=32, n_layers=2,
                         n_heads=4, max_position=128)
    params = gpt.setup(jax.random.key(0), None)[0]
    plain = positions_table(128)
    table = RowTable(plain.leaves, plain.rows, plain.write_row,
                     lambda pos: pos - (pos - 7) * (pos > 7), row_axis=2,
                     selected=True) if selected else plain
    assert table.selected == selected
    assert table.kernel_shaped != selected
    sm = SlotManager(_OneTable(gpt, table), params, 4, window=2)
    assert (sm.kv_write, sm.attn_read) == (
        ("scatter", "masked") if selected else ("kernel", "kernel"))


def test_attn_blocks_count_what_the_step_fetches(dots, monkeypatch):
    """Off the chip the model's step reads every row of its three tables
    under a mask, and ``attn_blocks`` says so. Where the model's own
    kernel takes the latents and the ring (as on the chip) the table
    stamps ``attn_read: model`` and counts what that kernel fetches: of
    the latents a live slot's whole blocks of rows up to its position
    (the CONTEXT, not the 8 rows the selection keeps), the whole ring of
    a live slot, and still every index key of every slot."""
    from bigdl_tpu.ops import latent_attention
    model, params, _, _ = dots
    sm = SlotManager(model, params, max_slots=3, window=2)
    held = 3 * (1 + 1 + 1)                  # 64, 64 and 128 rows a slot
    assert sm.attn_read == "masked"
    assert sm.attn_blocks() == (held, held)
    monkeypatch.setattr(latent_attention, "applies", lambda *a: True)
    monkeypatch.setattr(latent_attention, "BLOCK", 16)
    assert latent_attention.fetched_rows(np.zeros((3, 64, 128)))(
        np.array([0, 15, 16, 40])).tolist() == [16, 16, 32, 48]
    sm = SlotManager(model, params, max_slots=3, window=2)
    assert sm.attn_read == "model"
    assert sm.attn_blocks() == (3 * 1, held)     # no slot live: the keys
    sm.lengths[:2], sm.active[:2] = (40, 3), True
    # 48 and 16 rows of latents in blocks of 128, a ring of 128 rows each
    assert sm.attn_blocks() == (3 * 1 + (1 + 1) + (1 + 1), held)


# (g) the spans carry the host's own arithmetic -----------------------------
def test_step_and_prefill_spans_carry_the_rows_the_host_reckons(dots):
    model, params, _, _ = dots
    sm = SlotManager(model, params, max_slots=4, window=2)
    sm.admit([np.zeros(n, np.int32) for n in (5, 21)])

    def summed(n, cap):
        return sum(min(p + 1, cap) for p in range(n))

    assert sm.prefill_attrs == {
        "experts": "ragged_dot",        # 2 rows x 8 positions x 4
        "assignments": 4 * 26,
        "dsa_context_rows": summed(5, PMAX) + summed(21, PMAX),
        "dsa_selected_rows": summed(5, K) + summed(21, K),
        "swa_rows": summed(5, WIN) + summed(21, WIN)}
    sums = dict.fromkeys(("dsa_context_rows", "dsa_selected_rows",
                          "swa_rows"), 0)
    for name in sums:
        sums[name] = sm.prefill_attrs[name]
    held = 0.0
    for step in range(20):
        if step == 6:
            sm.admit([np.zeros(30, np.int32)])
            for name in sums:
                sums[name] += sm.prefill_attrs[name]
        if step == 14:
            sm.retire(0)
        pos = sm.lengths[sm.active].astype(int)
        want = {"dsa_context_rows": int(sum(p + 1 for p in pos)),
                "dsa_selected_rows": int(sum(min(p + 1, K) for p in pos)),
                "swa_rows": int(sum(min(p + 1, WIN) for p in pos))}
        sm.step()
        attrs = dict(sm.step_attrs)
        assert {k: attrs[k] for k in want} == want
        assert attrs["experts"] == "ragged_dot"
        assert attrs["assignments"] == 4 * len(pos)
        assert 0 <= attrs["experts_hit"] <= 4            # of the 4 held
        assert 0 <= attrs["assignments_held"] <= attrs["assignments"]
        held += attrs["assignments_held"]
        for name, n in want.items():
            sums[name] += n
    assert {k: sm.stats[k] for k in sums} == sums
    assert sm.stats["moe_assignments_held"] == pytest.approx(held)
    assert 0 < held < sm.stats["moe_assignments"]


def test_engine_stamps_the_rows_on_its_spans_and_sums_them(dots):
    model, params, _, _ = dots
    obs.default_tracer().clear()
    rng = np.random.default_rng(4)
    with ServingEngine(model, params, max_slots=2, max_queue=8) as eng:
        for h in [eng.submit(rng.integers(0, VOCAB, n).astype(np.int32), 20)
                  for n in (6, 33, 12)]:
            h.result(timeout=300)
        stats = dict(eng.stats)
    spans = obs.default_tracer().spans()
    # a ``serve/step`` describes the block it dispatches (``live`` and the
    # rows) and carries what came back with the block it reads, one older
    # (docs/observability.md): the last span of a busy stretch only reads
    every = [s.attrs for s in spans if s.name == "serve/step"]
    steps = [a for a in every if "live" in a]
    fills = [s.attrs for s in spans if s.name == "serve/prefill"]
    assert steps and fills
    for name in ("dsa_context_rows", "dsa_selected_rows", "swa_rows"):
        assert stats[name] == sum(a[name] for a in steps + fills)
    assert all(a["dsa_selected_rows"] <= K * a["live"]
               and a["dsa_selected_rows"] <= a["dsa_context_rows"]
               and a["swa_rows"] <= WIN * a["live"] for a in steps)
    reads = [a for a in every if "experts_hit" in a]
    assert len(reads) == len(steps)              # every block read once
    assert all("assignments_held" in a for a in reads)
    assert all(a in reads for a in every if a["ahead"] or "live" not in a)
    assert all(a["attn_blocks"] <= a["attn_blocks_table"] for a in steps)


# the selection by threshold is the selection by sort -----------------------
@pytest.mark.parametrize("k", [1, 8, 37, 64])
@pytest.mark.parametrize("kind", ["distinct", "tied", "short rows"])
def test_the_threshold_picks_what_top_k_picks(kind, k):
    """``top_k_mask`` against ``lax.top_k``: distinct scores; scores with
    many equal entries (of equal ones the first win, as ``top_k`` has it);
    rows with fewer real entries than ``k`` (``-inf`` is never picked)."""
    from bigdl_tpu.nn.latent import top_k_mask
    x = jax.random.normal(jax.random.key(k), (7, 64)) * 40
    if kind == "tied":
        x = jnp.round(x / 25.0)                       # a handful of values
    if kind == "short rows":
        seen = jnp.arange(64)[None, :] < jnp.asarray(
            [1, 2, 7, 8, 9, 40, 64])[:, None]
        x = jnp.where(seen, x, -jnp.inf)
    got = np.asarray(top_k_mask(x, k))
    vals, idx = jax.lax.top_k(x, k)
    want = np.zeros(x.shape, bool)
    want[np.arange(7)[:, None], np.asarray(idx)] = np.asarray(vals) > -np.inf
    assert (got == want).all()


# (h) the engine refuses what the model does not carry, by name ------------
@pytest.mark.parametrize("feature, kwargs", [
    ("paged", dict(paged=True)),
    ("spec_tokens", dict(spec_tokens=4)),
    ("lora", dict(lora=True)),
    ("int8_weights", dict(int8_weights=True)),
    ("int8_kv", dict(int8_kv=True)),
    ("tp", dict(tp=2)),
    ("kv_snapshot", dict(kv_snapshot=True, snapshot_dir="unused")),
])
def test_engine_refuses_a_feature_the_model_does_not_carry(dots, feature,
                                                           kwargs):
    model, params, _, _ = dots
    with pytest.raises(TypeError, match=f"'{feature}'"):
        ServingEngine(model, params, max_slots=2, **kwargs)


def test_a_table_that_is_not_whole_prompt_blocks_is_refused():
    with pytest.raises(ValueError, match="whole prefill blocks"):
        Dots3ForCausalLM(**dict(KW, max_position=60))
    with pytest.raises(ValueError, match="attention gate"):
        Dots3ForCausalLM(**dict(KW, attention_gate_type="elementwise"))


# the cell's buckets and sizes ----------------------------------------------
def test_the_cells_prompts_take_the_four_buckets_its_warm_up_builds():
    """Every prompt length of ``dots3-longctx-generate`` takes one of four
    prefill executables, 4096 to 32 768: the power-of-two class that the
    benchmark's warm-up runs the longest prompt of, so the window compiles
    none; the longest sequence fits the table; every width is the
    published one."""
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           "dots3-longctx-generate.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "dots3-note-prev-ep8-serve.json")) as f:
        config = json.load(f)
    kw = config["constructor_kwargs"]
    pmax = kw["max_position"]
    lo, hi = (traffic["prompt_tokens"][k] for k in ("min", "max"))
    assert (lo, hi, pmax) == (4096, 24576, 32768)
    assert hi + traffic["output_tokens"]["max"] == 28672 <= pmax
    buckets = {prompt_bucket(n, pmax) for n in range(lo, hi + 1, 61)}
    assert buckets == {4096, 8192, 16384, 32768}
    assert all(max(16, 1 << (n - 1).bit_length()) == prompt_bucket(n, pmax)
               for n in range(lo, hi + 1, 97))
    published = config["published"]
    for key, value in published.items():
        if key in kw and key not in ("num_hidden_layers", "layer_types",
                                     "n_routed_experts", "vocab_size"):
            assert kw[key] == value, key
    assert kw["layer_types"] == published["layer_types"][:5]
    assert (kw["experts_held"], kw["n_routed_experts"]) == (32, 256)
    assert kw["vocab_size"] * 8 == published["vocab_size"]
    model = Dots3ForCausalLM(**kw)
    shapes = jax.eval_shape(lambda k: model.setup(k, None)[0],
                            jax.random.key(0))
    n_params = sum(int(np.prod(s.shape))
                   for s in jax.tree_util.tree_leaves(shapes))
    assert 4.05e9 < n_params < 4.12e9          # ISSUE 34 reckons 4087 M
    counts = model.prefill_counts(np.array([12288]))
    assert counts["dsa_selected_rows"] == 2048 * 2049 // 2 + 10240 * 2048
    assert model.step_counts(np.array([12287]))["swa_rows"] == 513
