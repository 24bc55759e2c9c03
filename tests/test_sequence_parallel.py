"""Ring attention / Ulysses / dp x sp transformer tests on the 8-device mesh
(green-field capability — no reference analog; oracle = single-device
full_attention)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.parallel.sequence import (full_attention, ring_attention,
                                         ulysses_attention,
                                         MultiHeadAttention)


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.asarray(jax.devices()), axis_names=("seq",))


def _qkv(b=2, h=4, t=32, d=8, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return tuple(jax.random.normal(k, (b, h, t, d)) for k in ks)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.slow
    def test_matches_full_attention(self, mesh, causal):
        q, k, v = _qkv()
        ref = full_attention(q, k, v, causal=causal)
        sharding = NamedSharding(mesh, P(None, None, "seq", None))
        qs = jax.device_put(q, sharding)
        ks_ = jax.device_put(k, sharding)
        vs = jax.device_put(v, sharding)
        out = ring_attention(qs, ks_, vs, mesh, "seq", causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.slow
    def test_gradients_flow(self, mesh):
        q, k, v = _qkv(t=16)
        sharding = NamedSharding(mesh, P(None, None, "seq", None))
        args = [jax.device_put(a, sharding) for a in (q, k, v)]

        def loss(q, k, v):
            return jnp.sum(jnp.square(ring_attention(q, k, v, mesh, "seq")))

        def ref_loss(q, k, v):
            return jnp.sum(jnp.square(full_attention(q, k, v)))

        g = jax.grad(loss)(*args)
        g_ref = jax.grad(ref_loss)(q, k, v)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=5e-3, atol=1e-4)


class TestUlysses:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.slow
    def test_matches_full_attention(self, mesh, causal):
        q, k, v = _qkv(h=8)  # heads divisible by 8 devices
        ref = full_attention(q, k, v, causal=causal)
        sharding = NamedSharding(mesh, P(None, None, "seq", None))
        out = ulysses_attention(*[jax.device_put(a, sharding)
                                  for a in (q, k, v)], mesh, "seq",
                                causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_rejects_indivisible_heads(self, mesh):
        q, k, v = _qkv(h=6)
        with pytest.raises(ValueError, match="not divisible"):
            ulysses_attention(q, k, v, mesh, "seq")


class TestMHAModule:
    def test_local_mha_shapes_and_grad(self):
        mha = MultiHeadAttention(32, 4)
        mha.build(0, (2, 10, 32))
        x = jax.random.normal(jax.random.key(0), (2, 10, 32))
        y = mha.forward(x)
        assert y.shape == (2, 10, 32)
        gi = mha.backward(x, jnp.ones_like(y))
        assert gi.shape == x.shape


class TestSPTrainStep:
    @pytest.mark.slow
    def test_bert_dp_sp_trains(self):
        """2-way data x 4-way sequence parallel BERT-tiny step."""
        import bigdl_tpu.nn as nn
        from bigdl_tpu.models.transformer import BERT, make_sp_train_step
        from bigdl_tpu.optim import SGD

        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "seq"))
        model = BERT(vocab_size=50, hidden_size=16, n_layers=2, n_heads=2,
                     max_position=32,
                     sequence_parallel=("ring_inner", "seq", 4))
        model.build(0, jax.ShapeDtypeStruct((4, 32), jnp.int32))

        class _C(nn.Criterion):
            """Per-token regression proxy loss on the hidden states."""

            def apply(self, hidden, target):
                per_tok = jnp.mean(hidden, axis=-1)  # (B, T)
                return jnp.mean(jnp.square(per_tok
                                           - target.astype(jnp.float32)))

        step = make_sp_train_step(model, _C(), SGD(learningrate=0.1), mesh)
        opt_state = SGD(learningrate=0.1).init_state(model.params)
        rng = np.random.default_rng(0)
        x = jax.device_put(rng.integers(0, 50, (4, 32)).astype(np.int32),
                           NamedSharding(mesh, P("data", "seq")))
        y = jax.device_put(rng.integers(0, 2, (4, 32)).astype(np.int32),
                           NamedSharding(mesh, P("data", "seq")))
        params = model.params
        losses = []
        for _ in range(5):
            params, opt_state, loss = step(params, opt_state, x, y)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    @pytest.mark.slow
    def test_sp_matches_single_device(self):
        """The dp x sp BERT forward must equal the plain forward."""
        from bigdl_tpu.models.transformer import BERT
        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "seq"))
        kw = dict(vocab_size=40, hidden_size=16, n_layers=1, n_heads=2,
                  max_position=16)
        plain = BERT(**kw)
        plain.build(0, jax.ShapeDtypeStruct((2, 16), jnp.int32))
        sp = BERT(sequence_parallel=("ring_inner", "seq", 4), **kw)
        sp.params, sp.state = plain.params, plain.state  # same weights

        ids = jnp.asarray(np.random.default_rng(0).integers(0, 40, (2, 16)),
                          jnp.int32)
        ref, _ = plain.apply(plain.params, (), ids, training=False)

        from jax.sharding import PartitionSpec as P2

        def fwd(params, x):
            out, _ = sp.apply(params, (), x, training=False)
            return out

        from jax import shard_map
        sharded = shard_map(
            fwd, mesh=mesh, in_specs=(P2(), P2("data", "seq")),
            out_specs=P2("data", "seq"), check_vma=False)
        out = sharded(plain.params,
                      jax.device_put(ids, NamedSharding(mesh,
                                                        P2("data", "seq"))))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)


class TestFlashAuto:
    def test_flash_profitable_heuristic(self):
        """Auto kernel selection: pallas flash from the measured crossover
        points (causal S>=2048, bidirectional S>=8192), 128-tiled only."""
        from bigdl_tpu.parallel.sequence import flash_profitable
        assert flash_profitable(2048, causal=True)
        assert flash_profitable(8192, causal=False)
        assert not flash_profitable(512, causal=True)
        assert not flash_profitable(4096, causal=False)
        assert not flash_profitable(2050, causal=True)  # not 128-multiple

    def test_mha_defaults_to_auto(self, monkeypatch):
        monkeypatch.delenv("BIGDL_TPU_FLASH_ATTENTION", raising=False)
        from bigdl_tpu.parallel.sequence import MultiHeadAttention
        mha = MultiHeadAttention(16, 2)
        assert mha.use_flash is None  # auto mode resolves per shape

    @pytest.mark.slow
    def test_bert_for_mlm_forward(self):
        from bigdl_tpu.models.transformer import BertForMLM
        m = BertForMLM(vocab_size=50, hidden_size=16, n_layers=1,
                       n_heads=2, max_position=8)
        m.build(0, (2, 8))
        logits, _ = m.apply(m.params, (), jnp.zeros((2, 8), jnp.int32))
        assert logits.shape == (16, 50)


class TestSequenceAttentionDispatch:
    def test_picks_ulysses_when_heads_divide(self, mesh):
        from bigdl_tpu.parallel.sequence import sequence_attention
        q, k, v = _qkv(h=8)
        ref = full_attention(q, k, v)
        sharding = NamedSharding(mesh, P(None, None, "seq", None))
        out = sequence_attention(*[jax.device_put(a, sharding)
                                   for a in (q, k, v)], mesh, "seq")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_falls_back_to_ring_on_indivisible_heads(self, mesh):
        from bigdl_tpu.parallel.sequence import sequence_attention
        q, k, v = _qkv(h=6)  # 6 heads on 8 devices -> ring
        ref = full_attention(q, k, v, causal=True)
        sharding = NamedSharding(mesh, P(None, None, "seq", None))
        out = sequence_attention(*[jax.device_put(a, sharding)
                                   for a in (q, k, v)], mesh, "seq",
                                 causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)


class TestRemat:
    """jax.checkpoint integration: same numbers, recomputed activations."""

    def test_bert_remat_matches_plain(self):
        from bigdl_tpu.models.transformer import BERT
        ids = jnp.asarray(np.random.default_rng(0).integers(
            0, 32, (2, 16)), jnp.int32)
        plain = BERT(vocab_size=32, hidden_size=16, n_layers=2, n_heads=2,
                     max_position=16)
        plain.build(0, jax.ShapeDtypeStruct((2, 16), jnp.int32))
        rem = BERT(vocab_size=32, hidden_size=16, n_layers=2, n_heads=2,
                   max_position=16, remat=True)
        rem.params, rem.state = plain.params, plain.state

        def loss(m, p):
            out, _ = m.apply(p, (), ids, training=True)
            return jnp.sum(out ** 2)

        l0, g0 = jax.value_and_grad(lambda p: loss(plain, p))(plain.params)
        l1, g1 = jax.value_and_grad(lambda p: loss(rem, p))(plain.params)
        np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(g0),
                        jax.tree_util.tree_leaves(g1)):
            # the loss is ~5e2 while most grads are ~1e-4-1e-3 —
            # recompute reorders the cancellations, so roundoff lands at
            # ~1e-5 absolute on those leaves across jax/XLA versions;
            # the dominant ~1e2-scale grads must still match to rtol,
            # which is where a real remat bug would show
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=5e-5)

    def test_train_step_remat_matches_plain(self):
        import bigdl_tpu.nn as nn
        from bigdl_tpu.optim import SGD
        from bigdl_tpu.optim.optimizer import make_train_step
        model = (nn.Sequential().add(nn.Linear(8, 16)).add(nn.Tanh())
                 .add(nn.Linear(16, 3)).add(nn.LogSoftMax()))
        model.build(0, (4, 8))
        x = jnp.asarray(np.random.default_rng(1).standard_normal((4, 8)),
                        jnp.float32)
        y = jnp.asarray([0, 1, 2, 0], jnp.int32)
        crit = nn.ClassNLLCriterion()
        outs = []
        for flag in (False, True):
            # fresh copies: the fused step donates its input buffers
            p0 = jax.tree_util.tree_map(jnp.array, model.params)
            step = make_train_step(model, crit, SGD(learningrate=0.1),
                                   remat=flag)
            p, s, o, l = step(p0, model.state,
                              SGD(learningrate=0.1).init_state(p0),
                              jax.random.key(0), x, y)
            outs.append((float(l), p))
        np.testing.assert_allclose(outs[0][0], outs[1][0], rtol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(outs[0][1]),
                        jax.tree_util.tree_leaves(outs[1][1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6)
