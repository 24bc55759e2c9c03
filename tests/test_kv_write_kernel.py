"""``ops/kv_write.py``: the decode step's in-place K/V write, interpreted
on the CPU against the plain write it replaces (``jax.vmap`` over
``lax.dynamic_update_slice``), bit for bit in the live slots, and against
the tables as given in the others; the decode step with and without it;
what selects it; the serving step built with it, slots admitted and
retired mid-run; and, compiled for a described v5e chip at the GPT-2
medium and the LFM2 cells' sizes, that XLA moves no table to feed it."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from bigdl_tpu.ops import kv_write as kvw
from bigdl_tpu.ops.kv_write import plain_write

B, H, S, D = 6, 2, 256, 32


def operands(dtype, seed=0, shape=(B, H, S, D)):
    b, h, _, d = shape
    ks = jax.random.split(jax.random.key(seed), 4)
    draw = lambda k, s: jax.random.normal(k, s, jnp.float32).astype(dtype)
    return (draw(ks[0], shape), draw(ks[1], shape),
            draw(ks[2], (b, h, 1, d)), draw(ks[3], (b, h, 1, d)))


def bits(x):
    """The array's bit patterns, so -0.0 and 0.0 differ."""
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def same_bits(got, want):
    return all((bits(g) == bits(w)).all() for g, w in zip(got, want))


def direct(k_table, v_table, k_new, v_new, pos, live=None):
    return kvw.kv_write(k_table, v_table, k_new, v_new, pos, live,
                        interpret=True)


def donated(*args):
    # fresh copies: a donated operand is consumed where donation is real
    tables = [jnp.array(t) for t in args[:2]]
    return jax.jit(direct, donate_argnums=(0, 1))(*tables, *args[2:])


def scanned(k_table, v_table, k_new, v_new, pos, live=None):
    """Two steps in one ``lax.scan``, the second one position on
    (``steps_per_sync=2``'s shape: the mask stays, the positions move);
    compared with the plain write put through the same scan."""
    def run(write):
        def one(carry, step):
            kt, vt = write(*carry, k_new + step, v_new - step,
                           jnp.minimum(pos + step, S - 1))
            return (kt, vt), None

        steps = jnp.arange(2, dtype=jnp.int32)
        return lax.scan(one, (k_table, v_table), steps)[0]

    masked = lambda *a: direct(*a, live)
    return jax.jit(run, static_argnums=0)(masked), \
        jax.jit(run, static_argnums=0)(plain_write)


POSITIONS = {
    "all_first": [0] * B,
    "all_last": [S - 1] * B,
    # both sides of a sublane tile's edge and of a lane tile's edge
    "tile_edges_low": [7, 8, 15, 16, 127, 128],
    "tile_edges_high": [S - 129, S - 128, S - 17, S - 16, S - 9, S - 8],
    "mixed": [3, 250, 64, 129, 0, 200],
    "repeated": [40, 40, 41, 40, 255, 255],
    # clamped as ``dynamic_update_slice`` clamps: no block leaves the table
    "out_of_range": [-1, -300, S, S + 5, 2 ** 30, 12],
}


@pytest.mark.parametrize("how", ["direct", "donated", "scanned"])
@pytest.mark.parametrize("where", list(POSITIONS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kernel_writes_what_the_plain_write_writes(dtype, where, how):
    args = (*operands(dtype), jnp.asarray(POSITIONS[where], jnp.int32))
    if how == "scanned":
        got, want = scanned(*args)
    else:
        got = {"direct": direct, "donated": donated}[how](*args)
        want = plain_write(*args)
    assert same_bits(got, want)


MASKS = {
    "all_live": [1] * B,
    "none_live": [0] * B,
    "first_only": [1, 0, 0, 0, 0, 0],
    "last_only": [0, 0, 0, 0, 0, 1],
    # an output block must not go back before a body has filled it
    "leading_free": [0, 0, 0, 1, 1, 1],
    "trailing_free": [1, 1, 0, 0, 0, 0],
    "alternating": [1, 0, 1, 0, 1, 0],
}


def held_to_the_mask(got, want, tables, live):
    """Live slots bit for bit the plain write's, the others bit for bit
    the tables that went in."""
    live = np.asarray(live, bool)
    return all((bits(g)[live] == bits(w)[live]).all()
               and (bits(g)[~live] == bits(t)[~live]).all()
               for g, w, t in zip(got, want, tables))


@pytest.mark.parametrize("how", ["direct", "donated", "scanned"])
@pytest.mark.parametrize("which", list(MASKS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kernel_writes_the_live_slots_and_no_other(dtype, which, how):
    args = (*operands(dtype, seed=2), jnp.asarray(POSITIONS["mixed"],
                                                   jnp.int32))
    live = jnp.asarray(MASKS[which], bool)
    if how == "scanned":
        got, want = scanned(*args, live)
    else:
        got = {"direct": direct, "donated": donated}[how](*args, live)
        want = plain_write(*args)
    assert held_to_the_mask(got, want, args[:2], live)
    if which == "all_live":
        # ``live=None`` is this mask: the old result
        old = scanned(*args)[0] if how == "scanned" else direct(*args)
        assert same_bits(got, old) and same_bits(got, want)


@pytest.mark.parametrize("how", ["direct", "donated", "scanned"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_six_live_of_forty_eight(dtype, how):
    """The chat cell's occupancy at a small shape: more free slots than
    the kernel's ring of buffers is deep, live ones scattered among them,
    positions out of range among both."""
    shape = (48, H, S, 16)
    rng = np.random.default_rng(6)
    pos = rng.integers(-S, 2 * S, 48).astype(np.int32)
    live = np.zeros(48, bool)
    live[[1, 2, 17, 30, 31, 47]] = True
    args = (*operands(dtype, seed=4, shape=shape), jnp.asarray(pos))
    if how == "scanned":
        got, want = scanned(*args, jnp.asarray(live))
    else:
        got = {"direct": direct, "donated": donated}[how](
            *args, jnp.asarray(live))
        want = plain_write(*args)
    assert held_to_the_mask(got, want, args[:2], live)


def test_the_mask_is_an_operand_not_a_static_argument():
    """One trace and one executable serve every occupancy."""
    args = (*operands(jnp.float32), jnp.asarray(POSITIONS["mixed"],
                                                jnp.int32))
    write = jax.jit(direct)
    for which in MASKS:
        write(*args, jnp.asarray(MASKS[which], bool))
    assert write._cache_size() == 1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_only_the_written_row_changes(dtype):
    k_table, v_table, k_new, v_new = operands(dtype, seed=3)
    pos = np.asarray(POSITIONS["mixed"], np.int32)
    got = direct(k_table, v_table, k_new, v_new, jnp.asarray(pos))
    for table, new, out in ((k_table, k_new, got[0]),
                            (v_table, v_new, got[1])):
        table, new, out = bits(table), bits(new), bits(out)
        changed = (out != table).any(axis=(1, 3))            # (B, S)
        written = np.arange(S)[None, :] == pos[:, None]
        assert not (changed & ~written).any()
        assert (out[np.arange(B), :, pos, :] == new[:, :, 0, :]).all()


def test_negative_zero_and_extremes_survive():
    k_table, v_table, k_new, v_new = operands(jnp.float32, seed=5)
    odd = jnp.asarray([-0.0, 0.0, jnp.inf, -jnp.inf, 1e-45, 3.4e38],
                      jnp.float32)
    k_new = k_new.at[:, 0, 0, :6].set(odd)
    pos = jnp.asarray(POSITIONS["mixed"], jnp.int32)
    assert same_bits(direct(k_table, v_table, k_new, v_new, pos),
                     plain_write(k_table, v_table, k_new, v_new, pos))


@pytest.mark.parametrize("shape", [(2, 2, 100, 32), (2, 2, 128, 12)],
                         ids=["positions_not_128s", "head_size_not_8s"])
def test_shapes_without_whole_tiles_are_refused(shape):
    args = operands(jnp.float32, shape=shape)
    with pytest.raises(ValueError, match="kv_write needs"):
        direct(*args, jnp.zeros(shape[0], jnp.int32))


# ------------------------------------------------------------ selection --
class _Table:
    """What ``in_place_applies`` reads of an array."""

    def __init__(self, platform="tpu", dtype=jnp.float32,
                 shape=(48, 16, 1024, 64), major_to_minor=(0, 1, 3, 2)):
        from types import SimpleNamespace as NS
        self.dtype, self.shape = jnp.dtype(dtype), shape
        self.format = NS(layout=NS(major_to_minor=major_to_minor))
        self._devices = [NS(platform=platform)]

    def devices(self):
        return self._devices


@pytest.mark.parametrize("change,applies", [
    ({}, True),
    ({"dtype": jnp.bfloat16}, True),
    ({"platform": "cpu"}, False),
    ({"dtype": jnp.int8}, False),
    ({"dtype": jnp.float16}, False),
    # a head of 128 is kept row-major by the device: the view would copy
    ({"shape": (48, 16, 1024, 128), "major_to_minor": (0, 1, 2, 3)}, False),
    ({"shape": (48, 16, 1000, 64)}, False),
    ({"shape": (48, 16, 1024, 60)}, False),
    ({"dtype": jnp.bfloat16, "shape": (48, 16, 1024, 8)}, False),
], ids=["cell", "bf16", "cpu", "int8", "f16", "row_major", "ragged_positions",
        "ragged_head", "bf16_half_tile"])
def test_what_selects_the_kernel(change, applies):
    assert kvw.in_place_applies(_Table(**change)) is applies


def test_a_layout_or_the_cpu_keeps_the_plain_write():
    assert not kvw.in_place_applies(_Table(), layout=object())
    assert not kvw.in_place_applies(jnp.zeros((2, 2, 128, 64)))


# ---------------------------------------------------------- decode step --
def _mha():
    from bigdl_tpu.parallel.sequence import MultiHeadAttention
    mha = MultiHeadAttention(64, 2, causal=True)
    return mha, mha.make_params(jax.random.key(1), None)


def _gqa():
    from bigdl_tpu.models.lfm2 import GroupedQueryAttention
    gqa = GroupedQueryAttention(64, 4, 2)
    return gqa, gqa.make_params(jax.random.key(1), None)


def _filled(cache, dtype):
    return {k: jax.random.normal(jax.random.key(i), v.shape).astype(dtype)
            for i, (k, v) in enumerate(cache.items())}


@pytest.mark.parametrize("live", [None, [1, 1, 1, 1], [0, 1, 0, 1],
                                  [0, 0, 1, 0]],
                         ids=["no_mask", "all_live", "two_live", "one_live"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("layer", ["mha", "gqa"])
def test_decode_step_in_place_is_the_scatter_step(layer, dtype, live):
    """``_MHA`` and LFM2's attention: the live rows' outputs and tables
    are the scatter step's, a free row's table is the one that went in."""
    index = jnp.asarray([0, 127, 5, 64], jnp.int32)
    if layer == "mha":
        attn, params = _mha()
        x = jax.random.normal(jax.random.key(2), (4, 1, 64))
        cache = _filled(attn.init_cache(4, 128, dtype), dtype)
    else:
        attn, params = _gqa()
        x = jax.random.normal(jax.random.key(2), (4, 64))
        cache = _filled(attn.init_cache(4, 128, dtype), dtype)
    step = jax.jit(attn.decode_step, static_argnames="in_place")
    mask = None if live is None else jnp.asarray(live, bool)
    out, new = step(params, x, cache, index, in_place=True, live=mask)
    out0, new0 = step(params, x, cache, index, in_place=False, live=mask)
    live = np.ones(4, bool) if live is None else np.asarray(live, bool)
    assert held_to_the_mask([new["k"], new["v"]], [new0["k"], new0["v"]],
                            [cache["k"], cache["v"]], live)
    # the attention that follows reads the same bits; XLA fuses it
    # otherwise around an opaque call, so its sums round otherwise
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(out0, np.float32)[live],
                               rtol=0, atol=1e-5)


def test_a_scalar_index_ignores_in_place():
    mha, params = _mha()
    x = jax.random.normal(jax.random.key(2), (4, 1, 64))
    cache = mha.init_cache(4, 128)
    jaxpr = jax.make_jaxpr(
        lambda *a: mha.decode_step(*a, in_place=True))(params, x, cache, 5)
    assert "pallas_call" not in str(jaxpr)


def test_serving_step_says_which_write_it_built():
    from bigdl_tpu import obs
    from bigdl_tpu.serving import ServingEngine
    model, params = _gpt2_model()
    engine = ServingEngine(model, params, max_slots=2)
    try:
        assert engine.slots.kv_write == "scatter"          # not on a TPU
        engine.result(engine.submit(np.arange(5, dtype=np.int32), 3),
                      timeout=120)
    finally:
        engine.shutdown()
    # (the span that only drains the last block dispatches nothing)
    steps = [s for s in obs.default_tracer().spans()
             if s.name == "serve/step" and "live" in s.attrs]
    assert steps and all(s.attrs["kv_write"] == "scatter" for s in steps)


def _gpt2_model():
    from bigdl_tpu.models.gpt import gpt2_small
    model = gpt2_small(vocab_size=64, hidden_size=32, n_layers=2, n_heads=4,
                       max_position=128)
    return model, model.setup(jax.random.key(0), None)[0]


def _lfm2_model():
    from bigdl_tpu.models.lfm2 import LFM2ForCausalLM
    model = LFM2ForCausalLM(
        vocab_size=97, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=24,
        layer_types=["conv", "full_attention", "conv", "full_attention"],
        num_dense_layers=1, num_experts=8, num_experts_per_tok=2,
        num_attention_heads=4, num_key_value_heads=2, max_position=128)
    return model, model.setup(jax.random.key(0), None)[0]


def _served(model, params, prompts, lengths, slots):
    """Tokens a request, and ``(kv_write, kv_write_slots, live)`` of
    every ``serve/step`` span."""
    from bigdl_tpu import obs
    from bigdl_tpu.serving import ServingEngine
    obs.default_tracer().clear()
    engine = ServingEngine(model, params, max_slots=slots)
    try:
        handles = [engine.submit(p, n) for p, n in zip(prompts, lengths)]
        tokens = [list(engine.result(h, timeout=300)) for h in handles]
    finally:
        engine.shutdown()
    steps = [(s.attrs["kv_write"], s.attrs["kv_write_slots"],
              s.attrs["live"])
             for s in obs.default_tracer().spans()
             if s.name == "serve/step" and "live" in s.attrs]
    return tokens, steps


@pytest.mark.parametrize("family", [_gpt2_model, _lfm2_model],
                         ids=["gpt2", "lfm2"])
def test_serving_step_built_with_the_kernel_writes_live_slots_only(
        family, monkeypatch):
    """The whole step with the write kernel interpreted (the table's word
    is overridden: no CPU table says yes) and the masked read kept, so the
    mask reaches the kernel where no ``read`` does: more requests than
    slots and outputs of different lengths, so that slots are retired,
    stand free for some steps and are taken again mid-run."""
    from bigdl_tpu.serving import slots as slots_mod
    model, params = family()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, n).astype(np.int32)
               for n in (3, 70, 17, 90, 40, 9)]
    lengths = [12, 3, 7, 2, 9, 5]
    want, steps = _served(model, params, prompts, lengths, 4)
    assert steps and all(how == "scatter" and moved == 4
                         for how, moved, _ in steps)
    monkeypatch.setattr(slots_mod, "in_place_applies", lambda *a: True)
    got, steps = _served(model, params, prompts, lengths, 4)
    assert got == want
    assert steps and all(how == "kernel" and moved == live
                         for how, moved, live in steps)
    # some steps ran with slots free, and not always as many
    assert len({live for _, _, live in steps}) > 1
    assert min(live for _, _, live in steps) < 4


# ------------------------------------- compiled for the chip, no chip --
@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip to compile for (nothing runs). The TPU's
    compiler is loaded by this worker only, inside the fixture."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    for name, value in (("TPU_LOG_DIR", "disabled"),
                        ("TPU_ACCELERATOR_TYPE", "v5litepod-4"),
                        ("TPU_WORKER_HOSTNAMES", "localhost")):
        os.environ.setdefault(name, value)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip cannot be read back
    # from the persistent cache without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("shape,dtype", [
    ((48, 16, 1024, 64), jnp.float32),
    ((48, 16, 1024, 64), jnp.bfloat16),
    ((96, 8, 2048, 64), jnp.bfloat16),
], ids=["f32", "bf16", "lfm2_bf16"])
def test_compiled_at_the_cell_size_moves_no_table(one_chip, shape, dtype):
    b, h, s, d = shape
    at = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    write = jax.jit(
        lambda *a: kvw.kv_write(*a, interpret=False), donate_argnums=(0, 1))
    compiled = write.lower(
        at(shape, dtype), at(shape, dtype), at((b, h, 1, d), dtype),
        at((b, h, 1, d), dtype), at((b,), jnp.int32),
        at((b,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the device keeps this shape with its positions minor, which is what
    # ``in_place_applies`` asks of a table and the kernel's view relies on
    layout = re.search(rf"entry_computation_layout=\{{\(\w+\[{b},{h},{s},{d}\]"
                       r"\{([\d,]+)", text).group(1)
    assert layout == "2,3,1,0"
    moved = [line for line in text.splitlines()
             if re.search(rf"= \w+\[{b},{h},({s},{d}|{d},{s})\]\S* "
                          r"(copy|transpose|fusion)\(", line)]
    assert not moved, moved[:2]
    # both tables are written where they lie: no second table is held
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < 2 ** 20
    assert stats.alias_size_in_bytes >= 2 * np.prod(shape) * \
        jnp.dtype(dtype).itemsize
