"""``ops/decode_attention.py``: the decode step's length-bounded attention,
interpreted on the CPU against the masked reads it replaces
(``cached_attention`` and LFM2's ``_attend`` under its length mask); what
lies past a slot's length and what a free slot costs; the decode step
with and without it; what selects it; and, compiled for a described v5e
chip at the two serving cells' sizes, that XLA moves no table to feed it.

Tolerances. The kernel takes its products in float32 from the table's
dtype and sums in float32, and on the CPU ``cached_attention`` does the
same, so the two differ by the order of the sums: 2e-6 on outputs of
order 1. LFM2's ``_attend`` rounds the query and the softmax's weights to
the table's bfloat16 first, which the kernel does not: 2e-2."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from bigdl_tpu.ops import decode_attention as dat
from bigdl_tpu.ops import kv_write as kvw
from bigdl_tpu.parallel.sequence import cached_attention
# what ``applies`` reads of an array, and a described v5e chip to compile
# for: one definition of each, beside the write kernel's tests
from test_kv_write_kernel import _Table, one_chip  # noqa: F401

B, G, S, D = 6, 2, 384, 32
F32_TOL, BF16_OPERANDS_TOL = 2e-6, 2e-2

# one row each: the first position alone, both sides of a block's edge, a
# middle block, the whole table
LENGTHS = [1, 127, 128, 129, 300, S]


def operands(dtype, reps, seed=0, shape=(B, G, S, D)):
    b, g, _, d = shape
    ks = jax.random.split(jax.random.key(seed), 3)
    draw = lambda k, s: jax.random.normal(k, s, jnp.float32)
    return (draw(ks[0], (b, g, reps, d)), draw(ks[1], shape).astype(dtype),
            draw(ks[2], shape).astype(dtype))


def kernel(q, k, v, counts):
    return dat.decode_attention(q, k, v, jnp.asarray(counts, jnp.int32),
                                interpret=True)


def masked_read(q, k, v, counts):
    """``cached_attention``, one query a head at a time: (B, G, R, D)."""
    counts = jnp.asarray(counts, jnp.int32)
    return jnp.stack([cached_attention(q[:, :, r:r + 1], k, v, counts)[:, :, 0]
                      for r in range(q.shape[2])], axis=2)


def lfm2_read(q, k, v, counts):
    """LFM2's ``_attend`` under ``decode_step``'s length mask, before the
    output projection."""
    from bigdl_tpu.models.lfm2 import GroupedQueryAttention
    b, g, r, d = q.shape
    attn = GroupedQueryAttention(g * r * d, g * r, g)
    seen = jnp.arange(k.shape[2])[None, :] < jnp.asarray(counts)[:, None]
    out = attn._attend({"wo": jnp.eye(g * r * d, dtype=jnp.float32)},
                       q[:, :, :, None], k, v,
                       seen[:, None, None, None, :])          # (B, 1, G*R*D)
    return out.reshape(b, g, r, d)


@pytest.mark.parametrize("reps", [1, 4], ids=["r1", "r4"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kernel_reads_what_the_masked_read_reads(dtype, reps):
    q, k, v = operands(dtype, reps)
    got = kernel(q, k, v, LENGTHS)
    assert got.dtype == jnp.float32 and got.shape == q.shape
    np.testing.assert_allclose(got, masked_read(q, k, v, LENGTHS),
                               rtol=0, atol=F32_TOL)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, BF16_OPERANDS_TOL)],
                         ids=["f32", "bf16"])
def test_kernel_reads_what_lfm2_attends(dtype, tol):
    q, k, v = operands(dtype, 4, seed=1)
    np.testing.assert_allclose(kernel(q, k, v, LENGTHS),
                               lfm2_read(q, k, v, LENGTHS), rtol=0, atol=tol)


@pytest.mark.parametrize("count", LENGTHS)
def test_every_row_at_one_length(count):
    q, k, v = operands(jnp.float32, 1, seed=count)
    np.testing.assert_allclose(kernel(q, k, v, [count] * B),
                               masked_read(q, k, v, [count] * B),
                               rtol=0, atol=F32_TOL)


@pytest.mark.parametrize("poison", [np.nan, np.inf, -np.inf],
                         ids=["nan", "inf", "minus_inf"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_what_lies_past_a_length_changes_nothing(dtype, poison):
    q, k, v = operands(dtype, 4, seed=2)
    want = kernel(q, k, v, LENGTHS)
    past = (jnp.arange(S)[None, :]
            >= jnp.asarray(LENGTHS)[:, None])[:, None, :, None]
    got = kernel(q, jnp.where(past, poison, k), jnp.where(past, poison, v),
                 LENGTHS)
    assert np.isfinite(np.asarray(got)).all()
    assert (np.asarray(got) == np.asarray(want)).all()


def test_a_row_with_count_zero_is_zeros_and_its_neighbours_are_not_moved():
    q, k, v = operands(jnp.float32, 1, seed=3)
    want = np.asarray(kernel(q, k, v, LENGTHS))
    for dead in ([0], [2, 3], [0, 1, 2, 3, 4], [5], list(range(B))):
        counts = np.asarray(LENGTHS)
        counts[dead] = 0
        # what a free slot holds is another request's: make it loud
        kk = k.at[np.asarray(dead)].set(jnp.nan)
        got = np.asarray(kernel(q, kk, v, counts))
        assert (got[dead] == 0).all()
        live = [b for b in range(B) if b not in dead]
        assert (got[live] == want[live]).all()


def test_a_count_out_of_range_is_clamped():
    q, k, v = operands(jnp.float32, 1, seed=4)
    got = kernel(q, k, v, [-5, S + 1, 2 ** 30, 7, 0, S])
    want = kernel(q, k, v, [0, S, S, 7, 0, S])
    assert (np.asarray(got) == np.asarray(want)).all()


@pytest.mark.parametrize("heads,reps", [(4, 1), (3, 1), (2, 2), (1, 8),
                                        (2, 16)])
def test_however_many_heads_are_taken_together(heads, reps):
    # 4, 1, 2, 1 and 1 heads a loop
    q, k, v = operands(jnp.float32, reps, seed=5, shape=(3, heads, 256, 16))
    counts = [200, 0, 256]
    np.testing.assert_allclose(kernel(q, k, v, counts)[::2],
                               masked_read(q, k, v, counts)[::2],
                               rtol=0, atol=F32_TOL)


@pytest.mark.parametrize("shape", [(2, 2, 100, 32), (2, 2, 128, 12)],
                         ids=["positions_not_128s", "head_size_not_8s"])
def test_shapes_without_whole_tiles_are_refused(shape):
    q, k, v = operands(jnp.float32, 1, shape=shape)
    with pytest.raises(ValueError, match="decode_attention needs"):
        kernel(q, k, v, [1] * shape[0])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_under_scan_after_a_donated_write(dtype):
    """Two steps in one ``lax.scan`` (``steps_per_sync=2``'s shape): each
    writes a position through ``kv_write`` into the donated tables and
    attends up to it; the masked read put through the same scan."""
    q, k, v = operands(dtype, 1, seed=6)
    ks = jax.random.split(jax.random.key(7), 2)
    k_new = jax.random.normal(ks[0], (B, G, 1, D)).astype(dtype)
    v_new = jax.random.normal(ks[1], (B, G, 1, D)).astype(dtype)
    pos = jnp.asarray([0, 126, 127, 128, 299, S - 2], jnp.int32)
    active = jnp.asarray([True, True, False, True, True, True])

    def run(read, k, v):
        def one(carry, step):
            k, v = kvw.kv_write(*carry, k_new + step, v_new - step,
                                pos + step, interpret=True)
            return (k, v), read(q, k, v, jnp.where(active, pos + step + 1, 0))

        return lax.scan(one, (k, v), jnp.arange(2, dtype=jnp.int32))[1]

    fresh = lambda: (jnp.array(k), jnp.array(v))
    got = jax.jit(run, static_argnums=0, donate_argnums=(1, 2))(
        kernel, *fresh())
    want = jax.jit(run, static_argnums=0, donate_argnums=(1, 2))(
        masked_read, *fresh())
    live = np.asarray(active)
    np.testing.assert_allclose(got[:, live], want[:, live], rtol=0,
                               atol=F32_TOL)
    assert (np.asarray(got)[:, ~live] == 0).all()


def test_blocks_read_counts_the_live_slots_own_blocks():
    lengths = np.asarray([0, 126, 127, 128, 300, 5], np.int32)
    active = np.asarray([True, True, True, True, True, False])
    # length + 1 positions each: 1, 127, 128, 129, 301 -> 1 + 1 + 1 + 2 + 3
    assert dat.blocks_read(lengths, active) == 8
    assert dat.blocks_read(lengths, np.zeros(6, bool)) == 0


# ------------------------------------------------------------ selection --
@pytest.mark.parametrize("change,applies", [
    ({}, True),
    ({"dtype": jnp.bfloat16, "shape": (96, 8, 2048, 64)}, True),
    ({"platform": "cpu"}, False),
    ({"dtype": jnp.int8}, False),
    # a head of 128 is kept row-major by the device: the view would copy
    ({"shape": (48, 16, 1024, 128), "major_to_minor": (0, 1, 2, 3)}, False),
    ({"shape": (48, 16, 1000, 64)}, False),
    # two slots' K and V past the core's fast memory
    ({"shape": (4, 16, 8192, 64)}, False),
], ids=["gpt2_cell", "lfm2_cell", "cpu", "int8", "row_major",
        "ragged_positions", "slot_too_large"])
def test_what_selects_the_kernel(change, applies):
    assert dat.applies(_Table(**change)) is applies


def test_a_layout_or_the_cpu_keeps_the_masked_read():
    assert not dat.applies(_Table(), layout=object())       # tp > 1
    assert not dat.applies(jnp.zeros((2, 2, 128, 64)))


# ---------------------------------------------------------- decode step --
def test_gpt_decode_step_reads_through_the_kernel():
    from bigdl_tpu.parallel.sequence import MultiHeadAttention
    mha = MultiHeadAttention(64, 2, causal=True)
    params = mha.make_params(jax.random.key(1), None)
    x = jax.random.normal(jax.random.key(2), (4, 1, 64))
    cache = {k: jax.random.normal(jax.random.key(i), v.shape)
             for i, (k, v) in enumerate(mha.init_cache(4, 256).items())}
    index = jnp.asarray([0, 127, 128, 255], jnp.int32)
    live = jnp.asarray([True, True, False, True])
    step = jax.jit(mha.decode_step, static_argnames="in_place")
    out, new = step(params, x, cache, index, in_place=True,
                    read=jnp.where(live, index + 1, 0))
    out0, new0 = step(params, x, cache, index, in_place=True)
    assert all((new[n] == new0[n]).all() for n in ("k", "v"))
    live = np.asarray(live)
    np.testing.assert_allclose(out[live], out0[live], rtol=0, atol=1e-5)
    assert (np.asarray(out)[~live] == 0).all()     # zeros through ``wo``


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_lfm2_decode_step_reads_through_the_kernel(dtype):
    from bigdl_tpu.models.lfm2 import GroupedQueryAttention
    attn = GroupedQueryAttention(128, 8, 2)            # 4 queries a K/V head
    params = attn.make_params(jax.random.key(1), None)
    x = jax.random.normal(jax.random.key(2), (4, 128))
    cache = {k: jax.random.normal(jax.random.key(i), v.shape).astype(dtype)
             for i, (k, v) in enumerate(
                 attn.init_cache(4, 256, dtype).items())}
    pos = jnp.asarray([0, 127, 128, 255], jnp.int32)
    step = jax.jit(attn.decode_step, static_argnames="in_place")
    out, new = step(params, x, cache, pos, in_place=True, read=pos + 1)
    out0, new0 = step(params, x, cache, pos, in_place=True)
    assert all((new[n] == new0[n]).all() for n in ("k", "v"))
    tol = 1e-5 if dtype == jnp.float32 else BF16_OPERANDS_TOL
    np.testing.assert_allclose(out, out0, rtol=0, atol=tol)


def _serve(model, params, prompts, n_new, slots):
    from bigdl_tpu.serving import ServingEngine
    engine = ServingEngine(model, params, max_slots=slots)
    try:
        handles = [engine.submit(p, n_new) for p in prompts]
        return (engine.slots.attn_read,
                [engine.result(h, timeout=300) for h in handles])
    finally:
        engine.shutdown()


def test_serving_step_built_with_the_kernel_serves_the_same_tokens(
        monkeypatch):
    """The whole step with both kernels interpreted (the table's word is
    overridden: no CPU table says yes): more requests than slots, so that
    slots are free, taken again and at different lengths."""
    from bigdl_tpu.models.gpt import gpt2_small
    from bigdl_tpu.serving import slots as slots_mod
    model = gpt2_small(vocab_size=64, hidden_size=32, n_layers=2, n_heads=4,
                       max_position=256)
    params = model.setup(jax.random.key(0), None)[0]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, n).astype(np.int32)
               for n in (3, 120, 17, 126, 60)]
    how, want = _serve(model, params, prompts, 6, 3)
    assert how == "masked"
    monkeypatch.setattr(slots_mod, "in_place_applies", lambda *a: True)
    monkeypatch.setattr(slots_mod.decode_attention, "applies",
                        lambda *a: True)
    how, got = _serve(model, params, prompts, 6, 3)
    assert how == "kernel"
    assert [list(g) for g in got] == [list(w) for w in want]


# ------------------------------------- compiled for the chip, no chip --
@pytest.mark.parametrize("shape,reps,dtype", [
    ((48, 16, 1024, 64), 1, jnp.float32),
    ((96, 8, 2048, 64), 4, jnp.bfloat16),
], ids=["gpt2_cell", "lfm2_cell"])
def test_compiled_at_the_cell_size_moves_no_table(one_chip, shape, reps,
                                                  dtype):
    b, g, s, d = shape
    at = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    read = jax.jit(lambda *a: dat.decode_attention(*a, interpret=False))
    compiled = read.lower(at((b, g, reps, d), jnp.float32), at(shape, dtype),
                          at(shape, dtype), at((b,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the device keeps this shape with its positions minor, which is what
    # ``applies`` asks of a table and the kernel's view relies on
    layout = re.search(r"entry_computation_layout=\{\(\w+\[[\d,]+\]\S*, "
                       rf"\w+\[{b},{g},{s},{d}\]\{{([\d,]+)", text).group(1)
    assert layout == "2,3,1,0"
    moved = [line for line in text.splitlines()
             if re.search(rf"= \w+\[{b},{g},({s},{d}|{d},{s})\]\S* "
                          r"(copy|transpose|fusion)\(", line)]
    assert not moved, moved[:2]
    # nothing of a table's size is held beside the tables
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("shape", [(48, 50257), (96, 65536)],
                         ids=["gpt2_cell", "lfm2_cell"])
def test_sampled_branch_compiled_at_the_cell_size_sorts_nothing(
        one_chip, shape, monkeypatch):
    """The step's other kernel, kept beside these because one worker may
    describe the chip: ``select_tokens`` built with ``ops/sampling.py``
    passes Mosaic at both cells' logits tables (LFM2's step compiles the
    branch though its cell never takes it: a kernel over the scoped VMEM
    would fail that cell outright), and the compiled branch holds no sort
    and nothing of the table's size but the table's own padded copy."""
    from bigdl_tpu.ops import sampling
    from bigdl_tpu.serving.slots import select_tokens
    monkeypatch.setattr(sampling, "use_interpret", lambda: False)
    at = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    pick = jax.jit(lambda logits, temps, key: select_tokens(
        logits, temps, key, 40, 0.9, "kernel"))
    compiled = pick.lower(at(shape, jnp.float32), at(shape[:1], jnp.float32),
                          at((), jax.random.key(0).dtype)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "sample_cutoffs" in text
    assert not re.search(r"\bsort\(|top[_-]?k", text, re.I)
    assert sampling._vmem_bytes(shape[1]) <= 16 * 2 ** 20


@pytest.mark.parametrize("shape,heads,v_width", [
    ((36, 32768, 640), 128, 512),
    ((36, 640, 1152), 64, 1024),
], ids=["dots3_full_layer", "dots3_window_ring"])
def test_latent_read_compiled_at_the_cell_size(one_chip, shape, heads,
                                               v_width):
    """``ops/latent_attention.py`` (the read of a latent cache, PR 34) at
    ``dots3-longctx-generate``'s tables, their rows of 576 and 1088
    numbers kept in whole lanes (640 and 1152). Kept here, beside the
    other step kernels' compiles, so that one worker loads the TPU's
    compiler."""
    from bigdl_tpu.ops import latent_attention as lat
    b, rows, width = shape
    at = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    read = jax.jit(lambda *a: lat._latent_attention(*a, v_width, False))
    compiled = read.lower(
        at((b, heads, width), jnp.bfloat16), at(shape, jnp.bfloat16),
        at((b, rows), jnp.float32), at((b,), jnp.int32),
        at((b,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "latent_attention" in text
    # the table goes in as it lies: no copy of it around the call
    assert not re.search(rf"copy\(\w*\[{b},{rows},{width}\]", text)


def test_state_update_compiled_at_the_cell_size_moves_no_state(one_chip):
    """``ops/ssm_step.py`` (a Mamba-2 layer's decode-step state update)
    at ``nemotron3-reason-closed``'s table, 128 slots of 128 x 64 x 128
    float32, donated as the serving step donates it: the kernel takes the
    state where it lies and hands it back aliased, with no copy of it
    around the call and nothing of its size beside it. Kept here, beside
    the other step kernels' compiles, so that one worker loads the TPU's
    compiler."""
    from bigdl_tpu.ops import ssm_step
    shape = (128, 128, 64, 128)
    at = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    update = jax.jit(lambda *a: ssm_step.ssm_update(*a, interpret=False),
                     donate_argnums=0)
    compiled = update.lower(
        at(shape, jnp.float32), at(shape[:2], jnp.float32),
        at(shape[:3], jnp.float32), at((128, 128, 128), jnp.float32),
        at((128, 128, 128), jnp.float32), at((128,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ssm_step" in text
    assert not re.search(r"copy\(\w*\[128,128,64,128\]", text)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 4 * 128 * 128 * 64 * 128
    assert memory.temp_size_in_bytes < 2 ** 20


def test_eva_read_compiled_at_the_cell_size(one_chip):
    """``ops/eva_attention.py`` (EVA attention's two-table decode read)
    at ``evabyte-longdoc-generate``'s tables: 20 slots, a window of 2048
    rows and 1024 summaries of 32 heads of 128 in bfloat16, rows before
    heads. The tables go in as they lie, with no copy of one around the
    call and nothing of their size beside them. Kept here, beside the
    other step kernels' compiles, so that one worker loads the TPU's
    compiler."""
    from bigdl_tpu.ops import eva_attention as eva
    b, heads, head_dim = 20, 32, 128
    near, far = (b, 2048, heads, head_dim), (b, 1024, heads, head_dim)
    at = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    read = jax.jit(lambda q, *rest: eva.eva_attention(
        q, *rest, window=2048, chunk=16, interpret=False))
    compiled = read.lower(
        at((b, heads, head_dim), jnp.float32), at(near, jnp.bfloat16),
        at(near, jnp.bfloat16), at(far, jnp.bfloat16),
        at(far, jnp.bfloat16), at((b,), jnp.int32),
        at((b,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "eva_attention" in text
    moved = [line for line in text.splitlines()
             if re.search(r"= \w+\[20,(2048|1024),32,128\]\S* "
                          r"(copy|transpose|fusion)\(", line)]
    assert not moved, moved[:2]
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20
    assert eva._vmem_bytes((heads, head_dim), jnp.bfloat16, 24) \
        <= eva._VMEM_CEILING
