"""The prompt pass's grouped expert product (``ops/grouped_matmul.py``, the
Pallas grouped matmul, interpreted here) against ``lax.ragged_dot`` and a
plain product a group; and ``nn.RoutedExperts`` through it against the
same layer through ``ragged_dot`` and the dense reference layer
(``benchmarks/reference/lfm2moe.py``). The rule that picks the product
from the row count is held at the cells' own shapes.

Tolerances: float32 operands sum in another order, a few 1e-6 of values
of a few units (5e-5, as ``tests/test_lfm2_serving.py``); bfloat16
operands are rounded alike on both sides and differ by the order of the
float32 sums only (1e-5 relative to the largest)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from benchmarks.harness import weights
from benchmarks.reference import lfm2moe
from bigdl_tpu.models.dots3 import Dots3ForCausalLM
from bigdl_tpu.models.lfm2 import LFM2ForCausalLM
from bigdl_tpu.nn import moe
from bigdl_tpu.ops.grouped_matmul import grouped_matmul

TOL = 5e-5


def _per_group(lhs, rhs, sizes):
    """Each group's rows times its matrix in float64; the rest zeros."""
    lhs = np.asarray(lhs, np.float64)
    rhs = np.asarray(rhs, np.float64)
    out = np.zeros((lhs.shape[0], rhs.shape[2]))
    start = 0
    for g, n in enumerate(np.asarray(sizes)):
        out[start:start + n] = lhs[start:start + n] @ rhs[g]
        start += n
    return out


# (rows, contraction, output, group sizes, operand dtype, tiles):
# the trailing rows after the groups are never visited and come out zero
PRODUCTS = {
    "empty_groups": (256, 32, 24, [0, 70, 0, 0, 90, 60, 0, 36], "float32",
                     None),
    "trailing_rows": (512, 32, 24, [40, 3, 0, 77, 1, 50, 9, 20], "float32",
                      None),
    "rows_padded_to_the_tile": (200, 32, 24, [17, 0, 60, 3, 100],
                                "float32", (128, 32, 24)),
    "bfloat16_operands_float32_sums": (384, 256, 256,
                                       [100, 0, 150, 30, 60], "bfloat16",
                                       (128, 128, 128)),
    "every_row_held": (128, 16, 8, [64, 64], "float32", None),
    "no_row_held": (256, 32, 24, [0, 0, 0, 0], "float32", None),
    "one_row_in_the_last_group": (256, 32, 24, [0, 0, 0, 1], "float32",
                                  None),
}


@pytest.mark.parametrize("case", sorted(PRODUCTS))
def test_grouped_matmul_is_ragged_dot_and_a_product_a_group(case):
    m, k, n, sizes, dtype, tiles = PRODUCTS[case]
    keys = jax.random.split(jax.random.key(len(case)), 2)
    lhs = jax.random.normal(keys[0], (m, k)).astype(dtype)
    rhs = jax.random.normal(keys[1], (len(sizes), k, n)).astype(dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    got = jax.jit(lambda a, b, s: grouped_matmul(a, b, s, tiles))(
        lhs, rhs, sizes)
    assert got.shape == (m, n) and got.dtype == jnp.float32
    held = int(sizes.sum())
    want = jax.lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32)
    scale = float(jnp.abs(want).max(initial=0.0))
    tol = TOL if dtype == "float32" else 1e-5 * scale
    assert float(jnp.abs(got[:held] - want[:held]).max(initial=0.0)) < tol
    assert np.abs(np.asarray(got) - _per_group(lhs.astype(jnp.float32),
                                               rhs.astype(jnp.float32),
                                               sizes)).max() < tol
    assert not np.asarray(got[held:]).any()


def _layer_tree(layer, seed, dtype="float32"):
    shapes = jax.eval_shape(lambda k: layer.make_params(k, None),
                            jax.random.key(0))
    return weights.make_params(shapes, seed, {"std": 0.3, "bias_std": 0.1},
                               dtype=dtype)


# (experts, a token, first held, held, tokens, share of them live, weights'
# dtype): LFM2-shaped layers on both sides of the row rule, a dots3-shaped
# share (4 of 16 held, 8 a token), dead rows, bfloat16 weights
LAYERS = {
    "lfm2_step_rows": (8, 2, 0, 8, 96, 1.0, "float32"),
    "lfm2_prompt_rows": (8, 2, 0, 8, 256, 1.0, "float32"),
    "lfm2_prompt_rows_padded": (8, 2, 0, 8, 320, 0.3, "float32"),
    "dots3_share": (16, 8, 4, 4, 64, 1.0, "float32"),
    "dots3_share_bfloat16": (16, 8, 12, 4, 128, 0.75, "bfloat16"),
}


@pytest.mark.parametrize("case", sorted(LAYERS))
def test_routed_layer_through_either_product(case, monkeypatch):
    """``routed()`` through the product its rows pick, through
    ``ragged_dot`` (the rule's threshold moved out of reach), and the
    dense reference over the live rows: the same outputs and the same
    ``hit``; dead rows get zeros."""
    experts, k, first, count, tokens, live_share, dtype = LAYERS[case]
    layer = nn.RoutedExperts(16, 12, experts, k, first=first, count=count)
    tree = _layer_tree(layer, len(case), dtype)
    u = jax.random.normal(jax.random.key(3), (tokens, 16))
    live = np.arange(tokens) < int(live_share * tokens)
    product = moe.grouped_product(tokens * k)
    got, hit = jax.jit(layer.routed)(tree, u, live)
    monkeypatch.setattr(moe, "GROUPED_MATMUL_ROWS", 1 << 30)
    plain, plain_hit = jax.jit(layer.routed)(tree, u, live)
    assert product == ("gmm" if tokens * k >= 512 else "ragged_dot")
    assert int(hit) == int(plain_hit) >= 1
    ref = lfm2moe.routed_experts(
        tree, u, {"num_experts_per_tok": k, "experts_first": first},
        operand_dtype=None if dtype == "float32" else jnp.bfloat16)
    ref = np.where(live[:, None], np.asarray(ref), 0.0)
    if dtype == "float32":
        assert np.abs(np.asarray(got) - np.asarray(plain)).max() < TOL
        assert np.abs(np.asarray(got) - ref).max() < TOL
    else:
        # both programs round the same operands; the reference rounds
        # its own and sums in another order
        scale = float(np.abs(ref).max())
        assert np.abs(np.asarray(got) - np.asarray(plain)).max() \
            < 1e-5 * scale
        assert np.abs(np.asarray(got) - ref).max() < 2e-2 * scale
    assert not np.asarray(got)[~live].any()


def test_gradient_through_call_is_the_ragged_dot_ones(monkeypatch):
    """``jax.grad`` through ``RoutedExperts.call`` at prompt-pass rows
    (the grouped matmul's custom VJP) gives the gradient ``ragged_dot``
    gives, for the input and every expert matrix."""
    layer = nn.RoutedExperts(16, 12, 8, 2)
    tree = _layer_tree(layer, 11)
    x = jax.random.normal(jax.random.key(4), (2, 128, 16))     # 512 rows

    def loss(p, x):
        return jnp.sum(jnp.square(layer.call(p, x)))

    assert moe.grouped_product(x.size // 16 * 2) == "gmm"
    got = jax.jit(jax.grad(loss, (0, 1)))(tree, x)
    monkeypatch.setattr(moe, "GROUPED_MATMUL_ROWS", 1 << 30)
    want = jax.jit(jax.grad(loss, (0, 1)))(tree, x)
    for name in ("w1", "w3", "w2", "wg"):
        g, w = np.asarray(got[0][name]), np.asarray(want[0][name])
        assert np.abs(g - w).max() < TOL * max(1.0, np.abs(w).max()), name
        assert np.abs(w).max() > 0, name
    assert np.abs(np.asarray(got[1]) - np.asarray(want[1])).max() < TOL


def test_the_rule_at_the_cells_shapes():
    """A decode step's rows stay on ``ragged_dot`` (96 slots x 4 in LFM2,
    36 x 8 in dots3); every prompt pass's take the grouped matmul: 4 rows
    of buckets 32-512 x 4 in LFM2, blocks of 2048 positions x 8 in
    dots3."""
    assert moe.grouped_product(288) == moe.grouped_product(384) \
        == "ragged_dot"
    lfm2 = LFM2ForCausalLM(vocab_size=97, hidden_size=32,
                           intermediate_size=48, moe_intermediate_size=24,
                           layer_types=["conv", "conv"], num_dense_layers=1,
                           num_experts=8, num_experts_per_tok=4)
    assert lfm2.expert_rows(96, 1) == 384
    assert [moe.grouped_product(lfm2.expert_rows(4, b))
            for b in (32, 64, 128, 256, 512)] == ["gmm"] * 5
    assert [lfm2.expert_rows(4, b) for b in (32, 512)] == [512, 8192]
    dots3 = Dots3ForCausalLM(
        vocab_size=50, hidden_size=64, intermediate_size=96,
        layer_types=["full_attention", "sliding_attention"],
        first_k_dense_replace=1, n_routed_experts=16, num_experts_per_tok=8,
        max_position=8192, prefill_block=2048)
    assert dots3.expert_rows(36, 1) == 288
    assert {dots3.expert_rows(1, b) for b in (4096, 8192)} == {16384}
    assert moe.grouped_product(dots3.expert_rows(1, 4096)) == "gmm"
