"""Decoder-only GPT family (models/gpt.py) — causality, training,
generation, remat, and dp x sp compatibility on the virtual mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.models.gpt import GPT, GPTForCausalLM


def _tiny(**kw):
    cfg = dict(vocab_size=17, hidden_size=32, n_layers=2, n_heads=4,
               max_position=16)
    cfg.update(kw)
    return GPTForCausalLM(**cfg)


def test_causality_future_tokens_do_not_leak():
    """Changing token t+1..T must not change the logits at position t."""
    m = _tiny()
    m.build(0, (1, 8))
    ids = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    alt = ids.at[0, 5:].set(9)
    a, _ = m.apply(m.params, m.state, ids, training=False)
    b, _ = m.apply(m.params, m.state, alt, training=False)
    a = np.asarray(a).reshape(8, -1)
    b = np.asarray(b).reshape(8, -1)
    np.testing.assert_allclose(a[:5], b[:5], atol=1e-5)
    assert np.max(np.abs(a[5:] - b[5:])) > 1e-3  # suffix does change


def test_tied_embeddings_share_weights():
    m = _tiny(tie_embeddings=True)
    m.build(0, (1, 8))
    assert "head" not in m.params
    m2 = _tiny(tie_embeddings=False)
    m2.build(0, (1, 8))
    assert "head" in m2.params


def test_trains_next_token_pattern():
    """Overfit a deterministic cyclic sequence: loss -> ~0 and greedy
    generation reproduces the cycle."""
    from bigdl_tpu.optim import Adam
    from bigdl_tpu.optim.optimizer import make_train_step

    period = 5
    seq = np.arange(64) % period  # 0 1 2 3 4 0 1 2 ...
    ids = jnp.asarray(seq[None, :16], jnp.int32)
    labels = jnp.asarray(seq[1:17][None], jnp.int32).reshape(-1)

    m = _tiny(vocab_size=period, max_position=32)
    m.build(0, (1, 16))
    opt = Adam(learningrate=5e-3)
    step = make_train_step(m, nn.CrossEntropyCriterion(), opt)
    params, state = m.params, m.state
    opt_state = opt.init_state(params)
    rng = jax.random.key(0)
    loss = None
    for i in range(150):
        params, state, opt_state, loss = step(params, state, opt_state,
                                              rng, ids, labels)
    assert float(loss) < 0.05, float(loss)

    out = m.generate(params, np.asarray([0, 1, 2]), n_new=7)
    got = np.asarray(out)[0].tolist()
    assert got == [(i % period) for i in range(10)], got


def test_remat_matches_no_remat():
    m1 = _tiny(remat=False)
    m1.build(0, (2, 8))
    m2 = _tiny(remat=True)
    m2.build(0, (2, 8))
    m2.params = m1.params  # same weights
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 17, (2, 8)),
                      jnp.int32)
    a, _ = m1.apply(m1.params, (), ids, training=False)
    b, _ = m2.apply(m2.params, (), ids, training=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)

    def loss1(p):
        return jnp.sum(m1.apply(p, (), ids, training=False)[0] ** 2)

    def loss2(p):
        return jnp.sum(m2.apply(p, (), ids, training=False)[0] ** 2)

    g1 = jax.grad(loss1)(m1.params)
    g2 = jax.grad(loss2)(m1.params)
    for x, y in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-4, atol=1e-5)


def test_sequence_parallel_train_step():
    """GPT under the same dp x sp shard_map step BERT uses (ring causal
    attention + global positions per shard)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from bigdl_tpu.models.transformer import make_sp_train_step
    from bigdl_tpu.optim import SGD

    devs = np.asarray(jax.devices())
    assert devs.size == 8
    mesh = Mesh(devs.reshape(2, 4), ("data", "seq"))
    seq_len = 16  # 4 per seq shard
    m = GPTForCausalLM(vocab_size=11, hidden_size=16, n_layers=2,
                       n_heads=2, max_position=seq_len,
                       sequence_parallel=("ring_inner", "seq", 4))
    m.build(0, jax.ShapeDtypeStruct((4, seq_len), jnp.int32))

    class _TokenLoss(nn.Criterion):
        def apply(self, logits, target):
            per = jnp.mean(logits.reshape(target.shape + (-1,)), -1)
            return jnp.mean(jnp.square(per - target.astype(jnp.float32)))

    step = make_sp_train_step(m, _TokenLoss(), SGD(learningrate=0.1), mesh)
    opt = SGD(learningrate=0.1).init_state(m.params)
    sh = NamedSharding(mesh, P("data", "seq"))
    ids = jax.device_put(jnp.ones((4, seq_len), jnp.int32), sh)
    tgt = jax.device_put(jnp.zeros((4, seq_len), jnp.int32), sh)
    p2, opt, loss = step(m.params, opt, ids, tgt)
    assert np.isfinite(float(loss))


def test_generate_past_max_position_slides_window():
    """Generation beyond max_position crops to the last window instead of
    crashing on the position table."""
    m = _tiny(max_position=8)
    m.build(0, (1, 8))
    out = m.generate(m.params, np.asarray([1, 2, 3], np.int32), n_new=12)
    assert np.asarray(out).shape == (1, 15)
