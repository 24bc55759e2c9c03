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
from bigdl_tpu.ops.sampling import threshold_sample_logits

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


@pytest.mark.parametrize("shape", [(SLOTS, VOCAB), (12, VOCAB), (48, VOCAB),
                                   (4, VOCAB), (96, 65536)],
                         ids=["s8", "s12", "gpt2_cell", "prefill_window",
                              "lfm2_cell"])
@pytest.mark.parametrize("top_k,top_p", [(40, None), (None, 0.9), (40, 0.9)],
                         ids=["top_k", "top_p", "both"])
def test_threshold_sampling(top_k, top_p, shape):
    lower_for_tpu(
        lambda logits, key, temps, rows: threshold_sample_logits(
            logits, key, temps, top_k, top_p, rows=rows, interpret=False),
        S(shape, jnp.float32), S((), jax.random.key(0).dtype),
        S((shape[0], 1), jnp.float32), S((shape[0],), jnp.bool_))


@pytest.mark.parametrize("sampler,sorts", [("kernel", False), ("sort", True)])
def test_sampled_branch_built_with_the_kernel_holds_no_sort(sampler, sorts,
                                                            monkeypatch):
    """``select_tokens`` as the serving step traces it, lowered for a TPU
    at the chat cell's table: with the kernel no operation sorts or takes
    a top-k of the vocabulary; with the sorts (the control, so that the
    words looked for are the lowering's own) both are there."""
    from bigdl_tpu.ops import sampling
    from bigdl_tpu.serving.slots import select_tokens
    # the step asks the backend, which is the CPU here: compiled, as on
    # the chip
    monkeypatch.setattr(sampling, "use_interpret", lambda: False)
    text = lower_for_tpu(
        lambda logits, temps, key: select_tokens(logits, temps, key, 40, 0.9,
                                                 sampler),
        S((48, VOCAB), jnp.float32), S((48,), jnp.float32),
        S((), jax.random.key(0).dtype)).mlir_module()
    assert ("tpu_custom_call" in text) == (sampler == "kernel")
    assert ("stablehlo.sort" in text) == sorts
    assert ("top_k" in text) == sorts


@pytest.mark.parametrize("heads", [HEADS, 16], ids=["h12", "h16"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kv_write(dtype, heads):
    table = S((SLOTS, heads, SEQ, HEAD_DIM), dtype)
    new = S((SLOTS, heads, 1, HEAD_DIM), dtype)
    lower_for_tpu(lambda *a: kv_write(*a, interpret=False),
                  table, table, new, new, S((SLOTS,), jnp.int32))


@pytest.mark.parametrize("heads", [128, 16], ids=["h128", "h16"])
def test_ssm_step(heads):
    from bigdl_tpu.ops.ssm_step import ssm_update
    lower_for_tpu(lambda *a: ssm_update(*a, interpret=False),
                  S((SLOTS, heads, 64, 128), jnp.float32),
                  S((SLOTS, heads), jnp.float32),
                  S((SLOTS, heads, 64), jnp.float32),
                  S((SLOTS, heads, 128), jnp.float32),
                  S((SLOTS, heads, 128), jnp.float32),
                  S((SLOTS,), jnp.bool_))
