"""The prompt pass walked a block at a time (``models/prompt_blocks.py``),
on a toy pass whose rows are running sums carried from block to block."""

import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.models import prompt_blocks


@pytest.mark.parametrize("length,want", [(3, (3, 1)), (8, (4, 2)),
                                         (9, (4, 3)), (4, (4, 1))])
def test_block_count(length, want):
    assert prompt_blocks.block_count(4, length) == want


def _pass(cache, carries, ids, first, prompt_len):
    """Hidden rows: each position's running sum of ids over the prompt so
    far; the table (layer 0, positions on axis 1) gets each row's ids; the
    carry counts the blocks walked."""
    total, walked = carries
    sums = total[:, None] + jnp.cumsum(ids, axis=1).astype(jnp.float32)
    table = cache[0]["t"]
    table = table.at[:, first + jnp.arange(ids.shape[1])].set(ids)
    return (sums[..., None], [{"t": table}, cache[1]],
            (sums[:, -1], walked + 1))


def test_walk_gives_each_prompts_last_row_and_writes_its_table_back():
    ids = np.array([[1, 2, 3, 4, 5, 6, 0, 0, 0, 0],
                    [7, 8, 0, 0, 0, 0, 0, 0, 0, 0]], np.int32)
    lens = np.array([6, 2], np.int32)
    cache = [{"t": jnp.full((2, 16), -1, jnp.int32)},
             {"s": jnp.zeros((2, 3))}]
    walked = []

    def carries():
        return jnp.zeros((2,)), jnp.int32(0)

    def spy(cache, carried, ids_j, first, prompt_len):
        out = _pass(cache, carried, ids_j, first, prompt_len)
        walked.append(ids_j.shape)
        return out

    h_last, out = prompt_blocks.walk(4, 1, cache, jnp.asarray(ids),
                                     jnp.asarray(lens), spy, carries=carries,
                                     position_axes=[1])
    np.testing.assert_array_equal(np.asarray(h_last)[:, 0], [21.0, 15.0])
    table = np.asarray(out[0]["t"])
    # the bucket of 10 walks as 3 blocks of 4 (12 rows); only the first
    # two are walked (the longest prompt is 6), rows past 12 untouched
    np.testing.assert_array_equal(table[:, :8], ids[:, :8])
    assert (table[:, 8:] == -1).all()
    assert out[1]["s"].shape == (2, 3)
    assert walked == [(2, 4)]             # one trace of the loop's body


@pytest.mark.parametrize("max_position,ok", [(16, True), (3, True),
                                             (12, True), (10, False)])
def test_check_positions_wants_whole_blocks(max_position, ok):
    if ok:
        prompt_blocks.check_positions(max_position, 4)
    else:
        with pytest.raises(ValueError, match="not whole prefill blocks"):
            prompt_blocks.check_positions(max_position, 4)
