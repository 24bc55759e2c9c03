"""``ops/latent_attention.py`` interpreted on the CPU against the read it
replaces (every row of every slot scored and masked, true-float32
products): slots at both sides of a block's edge, at the table's end and
free; a bias that leaves a whole block, or every row, out; float32 and
bfloat16 tables. What selects it, and the decode step of
``models/dots3.py`` built with it, are in ``tests/test_dots3_serving.py``;
compiled for a described v5e at the cell's sizes it is in
``tests/test_decode_attention_kernel.py``, beside the other step kernels."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops import latent_attention as lat

B, H, R, W, V = 6, 8, 512, 72, 64
# both sides of a block's edge (blocks of 128 here), a middle block, the
# table's end, and the first row alone
POS = [0, 127, 128, 300, 511, 200]


def plain(q, table, bias, pos, live, v):
    s = jnp.einsum("bhc,bkc->bhk", q.astype(jnp.float32),
                   table.astype(jnp.float32), precision="highest")
    s = s + bias[:, None, :]
    ok = jnp.isfinite(s).any(-1, keepdims=True) & live[:, None, None]
    p = jnp.where(ok, jax.nn.softmax(jnp.where(ok, s, 0.0), -1), 0.0)
    return jnp.einsum("bhk,bkc->bhc", p, table[..., :v].astype(jnp.float32),
                      precision="highest")


def operands(dtype, share, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = (jax.random.normal(ks[0], (B, H, W)) * 0.3).astype(dtype)
    table = jax.random.normal(ks[1], (B, R, W)).astype(dtype)
    pos = jnp.asarray(POS, jnp.int32)
    counts = jax.random.bernoulli(ks[2], share, (B, R))
    counts = counts.at[jnp.arange(B), pos].set(True)       # its own row
    seen = jnp.arange(R)[None, :] <= pos[:, None]
    return q, table, jnp.where(counts & seen, 0.0, -jnp.inf), pos


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(lat, "BLOCK", 128)


@pytest.mark.parametrize("share", [1.0, 0.3, 0.01],
                         ids=["all", "a-third", "one-in-100"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_kernel_is_the_masked_read(dtype, tol, share):
    q, table, bias, pos = operands(dtype, share)
    live = jnp.ones(B, bool)
    got = lat.latent_attention(q, table, bias, pos, live, V, interpret=True)
    want = plain(q, table, bias, pos, live, V)
    assert got.shape == (B, H, V) and got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) <= tol


def test_a_free_slot_reads_nothing_and_gives_zeros():
    q, table, bias, pos = operands(jnp.float32, 0.5)
    live = jnp.asarray([True, False, True, False, True, True])
    # what a free slot holds may be anything: NaN there must not show
    table = jnp.where(live[:, None, None], table, jnp.nan)
    got = np.asarray(lat.latent_attention(q, table, bias, pos, live, V,
                                          interpret=True))
    assert np.isfinite(got).all()
    assert (got[1] == 0).all() and (got[3] == 0).all()
    want = np.asarray(plain(q, jnp.nan_to_num(table), bias, pos, live, V))
    assert np.abs(got - want).max() <= 2e-6


def test_rows_past_the_position_are_not_read():
    """Rows past a slot's last BLOCK are never fetched (NaN there changes
    nothing); inside its last block the bias keeps them out."""
    q, table, bias, pos = operands(jnp.float32, 0.5)
    live = jnp.ones(B, bool)
    want = lat.latent_attention(q, table, bias, pos, live, V, interpret=True)
    past = jnp.arange(R)[None, :] >= (pos[:, None] // 128 + 1) * 128
    poisoned = jnp.where(past[:, :, None], jnp.nan, table)
    got = lat.latent_attention(q, poisoned, bias, pos, live, V,
                               interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_a_slot_whose_rows_all_count_for_nothing_gives_zeros():
    q, table, bias, pos = operands(jnp.float32, 0.5)
    bias = bias.at[2].set(-jnp.inf)
    got = np.asarray(lat.latent_attention(q, table, bias, pos,
                                          jnp.ones(B, bool), V,
                                          interpret=True))
    assert (got[2] == 0).all() and np.isfinite(got).all()


def test_one_block_tables_and_the_ring():
    """A table of fewer rows than a block is one block (the window's ring
    of 640 rows at the published sizes)."""
    lat.BLOCK = 2048
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (3, 4, 40)) * 0.3
    table = jax.random.normal(ks[1], (3, 96, 40))
    valid = jnp.arange(96)[None, :] < jnp.asarray([1, 5, 96])[:, None]
    bias = jnp.where(valid, 0.0, -jnp.inf)
    pos = jnp.full((3,), 95, jnp.int32)
    live = jnp.ones(3, bool)
    got = lat.latent_attention(q, table, bias, pos, live, 32, interpret=True)
    assert float(jnp.abs(got - plain(q, table, bias, pos, live, 32)).max()) \
        <= 2e-6


def test_applies_only_on_a_tpu():
    table = jax.ShapeDtypeStruct((36, 32768, 640), jnp.bfloat16)
    assert not lat.applies(table)          # the CPU keeps the plain read
    assert not lat.applies(table, layout=object())
