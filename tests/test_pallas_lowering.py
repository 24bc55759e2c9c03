"""Lower every Pallas kernel for TPU from the CPU host, compiled
(``interpret=False``), at the GPT-2 124M shapes ``chip_smoke.py`` runs.

Interpret mode checks nothing about block shapes, and toy head counts
divide by every head block, so the CPU parity tests cannot see a BlockSpec
the TPU lowering refuses (the int8 paged kernel's scale planes at 12 heads
were one). ``jax.export`` with ``platforms=["tpu"]`` runs the jaxpr→Mosaic
lowering without a chip; Mosaic's own passes (vector layout, scoped VMEM)
still need libtpu — see ``chip_smoke.kernels_leg``.
"""

import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu.ops.flash_attention import flash_attention
from bigdl_tpu.ops.kv_write import kv_write
from bigdl_tpu.ops.paged_attention import paged_pool_attention
from bigdl_tpu.ops.sampling import fused_sample_logits

HEADS, HEAD_DIM, SEQ, VOCAB = 12, 64, 1024, 50257
SLOTS, PAGE_SIZE, CHUNK = 8, 16, 64
NUM_PAGES = SLOTS * SEQ // PAGE_SIZE


def lower_for_tpu(fn, *args):
    return jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)


def S(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("shape", [(1, HEADS, SEQ, HEAD_DIM),
                                   (1, 8, 8192, 64)], ids=["s1024", "s8192"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_flash_attention_forward_and_backward(shape, dtype):
    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=False).astype(jnp.float32).sum()

    x = S(shape, dtype)
    lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)


@pytest.mark.parametrize("heads", [HEADS, 3, 16],
                         ids=["h12", "h3_tp4_shard", "h16"])
@pytest.mark.parametrize("c", [1, CHUNK], ids=["decode", "chunk"])
@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
def test_paged_attention(int8, c, heads):
    plane = (NUM_PAGES, heads, PAGE_SIZE, HEAD_DIM)
    if int8:
        pool = {"k": S(plane, jnp.int8), "v": S(plane, jnp.int8),
                "k_scale": S(plane[:3], jnp.float32),
                "v_scale": S(plane[:3], jnp.float32)}
    else:
        pool = {"k": S(plane, jnp.float32), "v": S(plane, jnp.float32)}
    lower_for_tpu(
        lambda q, pool, table, q_pos: paged_pool_attention(
            q, pool, table, q_pos, interpret=False),
        S((SLOTS, heads, c, HEAD_DIM), jnp.float32), pool,
        S((SLOTS, SEQ // PAGE_SIZE), jnp.int32), S((SLOTS, c), jnp.int32))


@pytest.mark.parametrize("slots", [SLOTS, 12], ids=["s8", "s12"])
@pytest.mark.parametrize("top_k,top_p", [(40, None), (None, 0.9), (40, 0.9)],
                         ids=["top_k", "top_p", "both"])
def test_fused_sampling(top_k, top_p, slots):
    lower_for_tpu(
        lambda logits, key, temps: fused_sample_logits(
            logits, key, temps, top_k, top_p, interpret=False),
        S((slots, VOCAB), jnp.float32),
        S((), jax.random.key(0).dtype), S((slots, 1), jnp.float32))


@pytest.mark.parametrize("heads", [HEADS, 16], ids=["h12", "h16"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kv_write(dtype, heads):
    table = S((SLOTS, heads, SEQ, HEAD_DIM), dtype)
    new = S((SLOTS, heads, 1, HEAD_DIM), dtype)
    lower_for_tpu(lambda *a: kv_write(*a, interpret=False),
                  table, table, new, new, S((SLOTS,), jnp.int32))
