"""``ops/sampling.py``: the top-k and nucleus cuts by threshold.

The kernel (interpreted here, the same body the chip compiles) is held to
``models.gpt.sample_logits`` token for token on the same key at the
serving cells' own shapes, its cutoff to the value the sorts find bit for
bit, and ``select_tokens`` to the same tokens whichever sampler its
argument names. What ``sampled_logit_gap`` of the benchmark cannot see, a
nucleus cut left out, is planted here (PERF.md section 7.7).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.models.gpt import sample_logits
from bigdl_tpu.ops import sampling
from bigdl_tpu.serving.slots import select_tokens

TOP_K, TOP_P = 40, 0.9


def _logits(shape, seed=0, scale=2.0):
    return jax.random.normal(jax.random.key(seed), shape, jnp.float32) * scale


def _mixed_temps(s):
    """Two rows in five greedy, the others at 0.8: every block of 8 rows
    holds both kinds."""
    return jnp.where(jnp.arange(s) % 5 < 2, 0.0, 0.8).astype(jnp.float32)


def _sorted_cutoff(scaled, top_k, top_p):
    """The value ``sample_logits`` masks under, by its own sorts."""
    cut = np.full((scaled.shape[0], 1), -np.inf, np.float32)
    if top_k is not None:
        cut = np.asarray(jax.lax.top_k(scaled, top_k)[0][..., -1:])
        scaled = jnp.where(scaled < cut, -jnp.inf, scaled)
    if top_p is not None:
        ordered = jnp.sort(scaled, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(ordered, axis=-1)
        keep = jnp.sum((jnp.cumsum(probs, axis=-1) - probs < top_p)
                       .astype(jnp.int32), axis=-1, keepdims=True)
        cut = np.asarray(jnp.take_along_axis(ordered, keep - 1, axis=-1))
    return cut


@pytest.mark.parametrize("shape", [(48, 50257), (96, 65536), (4, 50257),
                                   (5, 1000)],
                         ids=["gpt2_cell", "lfm2_cell", "prefill_window",
                              "odd_rows_odd_vocab"])
def test_select_tokens_draws_the_same_tokens_as_the_sorts(shape):
    """Rows of temperature 0 mixed with 0.8, the engine's ``top_k`` and
    ``top_p``, a vocabulary that is no multiple of 128 (50257, 1000)."""
    logits, temps = _logits(shape, 1), _mixed_temps(shape[0])
    pick = jax.jit(select_tokens, static_argnums=(3, 4, 5))
    for seed in (0, 1):
        key = jax.random.key(seed)
        want, want_key = pick(logits, temps, key, TOP_K, TOP_P, "sort")
        got, got_key = pick(logits, temps, key, TOP_K, TOP_P, "kernel")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert (jax.random.key_data(got_key)
                == jax.random.key_data(want_key)).all()
    greedy = np.asarray(temps) == 0.0
    np.testing.assert_array_equal(
        np.asarray(got)[greedy], np.asarray(jnp.argmax(logits, -1))[greedy])


@pytest.mark.parametrize("top_k,top_p", [(40, 0.9), (40, None), (None, 0.9),
                                         (10, 0.5), (1, 0.9), (2000, 0.99)],
                         ids=["both", "top_k", "top_p", "tight", "k1",
                              "k_over_vocab"])
def test_cutoff_is_the_sorts_cutoff_bit_for_bit(top_k, top_p):
    scaled = _logits((11, 1000), 3) / 0.7
    want = _sorted_cutoff(scaled, top_k if top_k and top_k < 1000 else None,
                          top_p)
    got = np.asarray(sampling.cutoffs(scaled, top_k, top_p))
    assert got.shape == (11, 1)
    np.testing.assert_array_equal(got, want)
    # a value of the row itself, so the mask keeps it
    assert (np.asarray(scaled) == got).any(axis=-1).all()


def test_a_nucleus_of_three_of_the_forty_yields_only_those_three():
    """A row whose three best tokens hold 0.98 of the mass and whose two
    best hold 0.80: over 2000 keys only those three are drawn, and each
    of them is (a top-k of 40 alone would let 37 others through)."""
    v = 1000
    row = np.full(v, -4.0, np.float32)
    row[100:137] = 3.0 + 0.01 * np.arange(37)
    row[[7, 500, 999]] = [10.0, 9.5, 9.0]
    logits = jnp.asarray(np.stack([row, row[::-1]]))
    keys = jax.random.split(jax.random.key(11), 2000)
    draw = jax.jit(jax.vmap(lambda k: sampling.threshold_sample_logits(
        logits, k, 1.0, TOP_K, TOP_P)))
    toks = np.asarray(draw(keys))
    assert set(toks[:, 0]) == {7, 500, 999}
    assert set(toks[:, 1]) == {v - 1 - 7, v - 1 - 500, 0}
    # and in the softmax's own proportions, 0.51 : 0.31 : 0.19
    assert 0.45 < (toks[:, 0] == 7).mean() < 0.57
    loose = np.asarray(jax.jit(jax.vmap(lambda k: sample_logits(
        logits, k, 1.0, TOP_K, None)))(keys))
    assert len(set(loose[:, 0])) > 3


def test_a_temperature_zero_row_returns_the_argmax():
    logits = _logits((8, 300), 5)
    temps = jnp.asarray([0.0, 0.9, 0.0, 0.0, 1.3, 0.0, 0.0, 0.0])
    best = np.asarray(jnp.argmax(logits, -1))
    for seed in range(20):
        tok, _ = select_tokens(logits, temps, jax.random.key(seed), TOP_K,
                               TOP_P, "kernel")
        np.testing.assert_array_equal(np.asarray(tok)[[0, 2, 3, 5, 6, 7]],
                                      best[[0, 2, 3, 5, 6, 7]])
    # all greedy: the branch is not taken and the key is handed back
    key = jax.random.key(0)
    tok, out = select_tokens(logits, jnp.zeros(8), key, TOP_K, TOP_P,
                             "kernel")
    np.testing.assert_array_equal(np.asarray(tok), best)
    assert (jax.random.key_data(out) == jax.random.key_data(key)).all()


def test_a_block_of_rows_with_no_sampled_stream_is_left_alone():
    """24 rows are three blocks of 8: the middle one holds no sampled row
    and reads ``-inf`` (nothing cut, no pass made); a greedy row beside a
    sampled one rides in its block and gets its own cutoff."""
    scaled = _logits((24, 700), 7)
    rows = np.zeros(24, bool)
    rows[[1, 6, 17]] = True
    got = np.asarray(sampling.cutoffs(scaled, TOP_K, TOP_P, rows=rows))
    want = _sorted_cutoff(scaled, TOP_K, TOP_P)
    assert np.isneginf(got[8:16]).all()
    np.testing.assert_array_equal(got[:8], want[:8])
    np.testing.assert_array_equal(got[16:], want[16:])
    # no row drawn from: every block skipped
    none = sampling.cutoffs(scaled, TOP_K, TOP_P, rows=np.zeros(24, bool))
    assert np.isneginf(np.asarray(none)).all()


def test_rows_of_equal_logits_and_of_minus_infinity_cut_nothing_they_hold():
    """A free slot's row of the logits table is all zeros, and a masked
    vocabulary holds ``-inf``: ties at the cut are all kept, as
    ``sample_logits`` keeps them."""
    flat = jnp.zeros((3, 200), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(sampling.cutoffs(flat, TOP_K, TOP_P)), np.zeros((3, 1)))
    holes = _logits((4, 200), 9).at[:, 50:].set(-jnp.inf)
    got = np.asarray(sampling.cutoffs(holes, TOP_K, TOP_P))
    np.testing.assert_array_equal(got, _sorted_cutoff(holes, TOP_K, TOP_P))
    key = jax.random.key(2)
    np.testing.assert_array_equal(
        np.asarray(sampling.threshold_sample_logits(holes, key, 0.8, TOP_K,
                                                    TOP_P)),
        np.asarray(sample_logits(holes, key, 0.8, TOP_K, TOP_P)))


def test_no_cut_asked_for_is_a_plain_categorical_draw():
    logits, key = _logits((3, 90), 4), jax.random.key(6)
    assert np.isneginf(np.asarray(sampling.cutoffs(logits))).all()
    np.testing.assert_array_equal(
        np.asarray(sampling.threshold_sample_logits(logits, key, 0.9)),
        np.asarray(sample_logits(logits, key, 0.9)))


def test_applies_reads_the_table_it_is_given():
    """No CPU table is taken (the serving executables keep the sorts off
    the chip), nor one that is not float32, whatever the device."""
    table = jnp.zeros((4, 512), jnp.float32)
    assert not sampling.applies(table)
    assert not sampling.applies(table.astype(jnp.bfloat16))
    with pytest.raises(ValueError, match="float32"):
        sampling.cutoffs(table.astype(jnp.bfloat16), TOP_K, TOP_P)
    # a row block of the cells' vocabularies fits the chip's fast memory
    for vocab in (50257, 65536, 262144):
        assert sampling._vmem_bytes(vocab) <= sampling._VMEM_CEILING
