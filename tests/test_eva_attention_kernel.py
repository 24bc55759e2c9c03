"""``ops/eva_attention.py`` interpreted on the CPU against the read it
replaces (``EvaAttention._attend`` under the decode step's masks: every
row of the window and of the summaries scored, one softmax over both):
positions on a window's first and last row, inside the first window (no
summary seen), on a chunk's closing position, and a mix; float32 and
bfloat16 tables; a free slot; what lies past a slot's last block; what
selects it. The decode step of ``models/evabyte.py`` built with it is in
``tests/test_evabyte_serving.py``; compiled for a described v5e at the
cell's sizes it is in ``tests/test_decode_attention_kernel.py``, beside
the other step kernels.

Tolerances. Both reads take products from the table's dtype with float32
sums and round the softmax's weights to the table's dtype before V, so
they differ by the order of float32 sums: 2e-6 on outputs of order 1. In
bfloat16 a weight that lands on the other side of a rounding moves an
output by up to 2^-9 of that weight's share: 2e-5 allows that and no
wrong row (which moves an output by a good part of its spread, 0.5 to
3)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.models.evabyte import EvaAttention
from bigdl_tpu.ops import eva_attention as eva

# two blocks of window, one of summaries (four windows in chunks of 8)
B, H, D, W, C = 6, 4, 16, 256, 8
S = 4 * W // C
POSITIONS = {
    "window_first_row": [W, 2 * W, 3 * W, 0, W, 2 * W],
    "window_last_row": [W - 1, 2 * W - 1, 3 * W - 1, 4 * W - 1, W - 1,
                        2 * W - 1],
    "first_window": [0, 1, 100, 127, 128, W - 1],
    "chunk_closing": [C - 1, W + C - 1, 2 * W + 127, 3 * W + 135,
                      4 * W - 9, 135],
    "mixed": [0, W - 1, W, 700, 4 * W - 1, 300],
}
TOLS = [(jnp.float32, 2e-6), (jnp.bfloat16, 2e-5)]


def operands(dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (B, H, D))
    near = [jax.random.normal(k, (B, W, H, D)).astype(dtype) for k in ks[1:3]]
    far = [jax.random.normal(k, (B, S, H, D)).astype(dtype) for k in ks[3:5]]
    return q, near, far


def kernel(q, near, far, pos, live):
    return eva.eva_attention(q, *near, *far, jnp.asarray(pos, jnp.int32),
                             jnp.asarray(live), window=W, chunk=C,
                             interpret=True)


def masked_read(q, near, far, pos):
    """``EvaAttention.decode_step``'s read before the kernel: (B, H, D)."""
    pos = jnp.asarray(pos, jnp.int32)
    local = jnp.arange(W)[None, :] <= (pos % W)[:, None]
    seen = jnp.arange(S)[None, :] < ((pos // W) * (W // C))[:, None]
    return EvaAttention(H * D, H, W, C)._attend(
        q, *near, *far, local[:, None, :], seen[:, None, :])


@pytest.mark.parametrize("where", list(POSITIONS))
@pytest.mark.parametrize("dtype,tol", TOLS, ids=["f32", "bf16"])
def test_kernel_is_the_masked_read(dtype, tol, where):
    q, near, far = operands(dtype)
    pos = POSITIONS[where]
    got = kernel(q, near, far, pos, np.ones(B, bool))
    assert got.shape == (B, H, D) and got.dtype == jnp.float32
    assert float(jnp.abs(got - masked_read(q, near, far, pos)).max()) <= tol


@pytest.mark.parametrize("dtype,tol", TOLS, ids=["f32", "bf16"])
def test_a_free_slot_reads_nothing_and_gives_zeros(dtype, tol):
    q, near, far = operands(dtype, seed=1)
    pos = POSITIONS["mixed"]
    live = np.array([True, False, True, True, False, True])
    # what a free slot holds may be anything: NaN there must not show
    free = jnp.asarray(~live)[:, None, None, None]
    poisoned = [[jnp.where(free, jnp.nan, t).astype(dtype) for t in ts]
                for ts in (near, far)]
    got = np.asarray(kernel(q, *poisoned, pos, live))
    assert np.isfinite(got).all()
    assert (got[~live] == 0).all()
    want = np.asarray(masked_read(q, near, far, pos))
    assert np.abs(got[live] - want[live]).max() <= tol


@pytest.mark.parametrize("table", ["window", "summaries"])
def test_rows_past_a_slots_last_block_change_nothing(table):
    """Blocks past a slot's last are never fetched (NaN there changes
    nothing, bit for bit); inside its last block the row mask keeps the
    rest out."""
    q, near, far = operands(jnp.float32, seed=2)
    pos = np.asarray(POSITIONS["mixed"])
    live = np.ones(B, bool)
    want = np.asarray(kernel(q, near, far, pos, live))
    if table == "window":
        rows, counts, tabs = W, pos % W + 1, near
    else:
        rows, counts, tabs = S, (pos // W) * (W // C), far
    past = np.arange(rows)[None, :] >= eva.fetched_rows(counts)[:, None]
    assert past.any()
    past = jnp.asarray(past)[:, :, None, None]
    poisoned = [jnp.where(past, jnp.nan, t) for t in tabs]
    args = (poisoned, far) if table == "window" else (near, poisoned)
    assert np.array_equal(np.asarray(kernel(q, *args, pos, live)), want)


def test_fetched_rows_are_whole_blocks():
    rows = np.array([0, 1, 127, 128, 129, 2048])
    assert list(eva.fetched_rows(rows)) == [0, 128, 128, 128, 256, 2048]


@pytest.mark.parametrize("shape,dtype,layout", [
    ((20, 2048, 32, 128), jnp.bfloat16, None),
    ((20, 1024, 32, 128), jnp.bfloat16, None),
    ((20, 2048, 32, 128), jnp.float32, None),
    ((20, 2048, 32, 128), jnp.bfloat16, object()),
], ids=["window", "summaries", "f32", "mesh_layout"])
def test_applies_only_on_a_tpu(shape, dtype, layout):
    """The CPU, and a table laid out over a mesh, keep the masked read."""
    assert not eva.applies(jax.ShapeDtypeStruct(shape, dtype), layout)


@pytest.mark.parametrize("shape,dtype", [
    ((2, 200, 4, 16), jnp.float32),
    ((2, 256, 6, 16), jnp.float32),
    ((2, 256, 4, 16), jnp.float16),
], ids=["ragged_rows", "heads_not_a_power_of_two", "f16"])
def test_tables_the_kernel_cannot_read_are_refused(shape, dtype):
    table = jnp.zeros(shape, dtype)
    q = jnp.zeros((shape[0], shape[2], shape[3]))
    pos = jnp.zeros(shape[0], jnp.int32)
    with pytest.raises(ValueError, match="eva_attention needs"):
        eva.eva_attention(q, table, table, table, table, pos,
                          jnp.ones(shape[0], bool), window=shape[1], chunk=8,
                          interpret=True)
