"""Pallas flash-attention kernel (ops/flash_attention.py).

On CPU the kernels run in pallas interpret mode — identical code to the TPU
path. Oracle: ``parallel/sequence.full_attention`` (the same oracle the
ring/Ulysses kernels verify against).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu.ops.flash_attention import flash_attention
from bigdl_tpu.parallel.sequence import full_attention


def _qkv(b, h, s, d, seed=0, dtype="float32"):
    rs = np.random.RandomState(seed)
    return [jnp.asarray(rs.randn(b, h, s, d).astype(dtype))
            for _ in range(3)]


@pytest.mark.parametrize("causal", [False, True])
def test_matches_full_attention(causal):
    q, k, v = _qkv(2, 3, 256, 64)
    o1 = np.asarray(flash_attention(q, k, v, causal=causal))
    o2 = np.asarray(full_attention(q, k, v, causal=causal))
    np.testing.assert_allclose(o1, o2, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.slow
def test_gradients_match(causal):
    q, k, v = _qkv(1, 2, 256, 32, seed=1)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            jnp.sin(fn(q, k, v, causal=causal)))

    g1 = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(full_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_uneven_blocks():
    # seq 384 with default 512 blocks -> block shrinks to the sequence
    q, k, v = _qkv(1, 1, 384, 16, seed=2)
    o1 = np.asarray(flash_attention(q, k, v))
    o2 = np.asarray(full_attention(q, k, v))
    np.testing.assert_allclose(o1, o2, rtol=2e-4, atol=2e-5)


def test_non_dividing_block_auto_fits():
    # s=300 with requested 128 blocks: no 128-multiple divides it, so
    # fit_block takes the whole sequence as one block
    q, k, v = _qkv(1, 1, 300, 16)
    o1 = np.asarray(flash_attention(q, k, v, block_q=128, block_k=128))
    o2 = np.asarray(full_attention(q, k, v))
    np.testing.assert_allclose(o1, o2, rtol=2e-4, atol=2e-5)


def test_128_multiple_but_not_512():
    # the MHA gate passes t % 128 == 0; 640 must work with default blocks
    q, k, v = _qkv(1, 2, 640, 32, seed=6)
    for causal in (False, True):
        o1 = np.asarray(flash_attention(q, k, v, causal=causal))
        o2 = np.asarray(full_attention(q, k, v, causal=causal))
        np.testing.assert_allclose(o1, o2, rtol=2e-4, atol=2e-5)


def test_bf16_inputs():
    q, k, v = [t.astype(jnp.bfloat16) for t in _qkv(1, 2, 256, 64, seed=3)]
    o1 = np.asarray(flash_attention(q, k, v).astype(jnp.float32))
    o2 = np.asarray(full_attention(q, k, v).astype(jnp.float32))
    assert o1.dtype == np.float32
    np.testing.assert_allclose(o1, o2, rtol=0.02, atol=0.02)


def test_mha_flash_path_matches_xla_path():
    from bigdl_tpu.parallel.sequence import MultiHeadAttention
    x = jnp.asarray(np.random.RandomState(4).randn(2, 128, 64)
                    .astype("float32"))
    mha = MultiHeadAttention(64, 4, use_flash=True)
    mha.build(0, (2, 128, 64))
    mha_ref = MultiHeadAttention(64, 4, use_flash=False)
    mha_ref.params = mha.params
    mha_ref.build(0)
    o1 = np.asarray(mha.forward(x))
    o2 = np.asarray(mha_ref.forward(x))
    np.testing.assert_allclose(o1, o2, rtol=2e-4, atol=2e-5)


def test_mha_flash_falls_back_on_unaligned_seq():
    from bigdl_tpu.parallel.sequence import MultiHeadAttention
    x = jnp.asarray(np.random.RandomState(5).randn(2, 100, 64)
                    .astype("float32"))  # 100 not a multiple of 128
    mha = MultiHeadAttention(64, 4, use_flash=True)
    mha.build(0, (2, 100, 64))
    assert mha.forward(x).shape == (2, 100, 64)


@pytest.mark.slow
def test_ring_flash_matches_full_attention():
    """Ring attention on the pallas flash kernel (distributed long-context
    on the hot-op kernel): per-chunk flash + logsumexp combine must equal
    single-device attention, forward and backward, causal and not."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    from bigdl_tpu.parallel.sequence import ring_attention

    devs = np.asarray(jax.devices())
    mesh = Mesh(devs[:4], ("seq",))
    rs = np.random.RandomState(7)
    q, k, v = [jnp.asarray(rs.randn(1, 2, 512, 32).astype("float32"))
               for _ in range(3)]
    for causal in (False, True):
        o_ring = ring_attention(q, k, v, mesh, "seq", causal=causal,
                                use_flash=True)
        o_full = full_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(o_ring), np.asarray(o_full),
                                   rtol=2e-4, atol=2e-5)

        g1 = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(
            ring_attention(q, k, v, mesh, "seq", causal=causal,
                           use_flash=True))), argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(
            full_attention(q, k, v, causal=causal))),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)


def test_ulysses_flash_matches_full_attention():
    import jax
    from jax.sharding import Mesh
    from bigdl_tpu.parallel.sequence import ulysses_attention

    devs = np.asarray(jax.devices())
    mesh = Mesh(devs[:4], ("seq",))
    rs = np.random.RandomState(8)
    q, k, v = [jnp.asarray(rs.randn(1, 4, 512, 32).astype("float32"))
               for _ in range(3)]
    o1 = ulysses_attention(q, k, v, mesh, "seq", causal=True, use_flash=True)
    o2 = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-4, atol=2e-5)
