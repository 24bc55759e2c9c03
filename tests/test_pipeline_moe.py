"""Pipeline parallelism + mixture-of-experts tests on the 8-device mesh.

Green-field TPU capabilities (no reference analog — SURVEY.md section 2.6:
the reference is data-parallel only); oracles are single-device sequential
application / dense top-k routing.
"""

import numpy as np
import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import bigdl_tpu.nn as nn


@pytest.fixture(scope="module")
def pipe_mesh():
    return Mesh(np.asarray(jax.devices())[:4], ("pipe",))


class TestPipeline:
    def _setup(self, n_stages=4, mb=2, d=16):
        stage = nn.Sequential().add(nn.Linear(d, d)).add(nn.Tanh())
        stage.build(0, (mb, d))
        rng = np.random.default_rng(0)
        stacked = jtu.tree_map(
            lambda v: jnp.asarray(
                rng.standard_normal((n_stages,) + v.shape) * 0.3),
            stage.params)
        return stage, stacked, rng

    @pytest.mark.slow
    def test_matches_sequential_oracle_and_trains(self, pipe_mesh):
        from bigdl_tpu.parallel.pipeline import make_pipeline_train_step
        from bigdl_tpu.optim import SGD
        n_stages, n_micro, mb, d = 4, 8, 2, 16
        stage, stacked, rng = self._setup(n_stages, mb, d)
        crit = nn.MSECriterion()
        factory = make_pipeline_train_step(stage, crit,
                                           SGD(learningrate=0.1),
                                           pipe_mesh, n_micro=n_micro)
        step, sharded, opt_sh = factory(stacked)
        xs = jnp.asarray(rng.standard_normal((n_micro, mb, d)), jnp.float32)
        ys = jnp.asarray(rng.standard_normal((n_micro, mb, d)), jnp.float32)
        new_params, new_opt, loss = step(sharded, opt_sh, xs, ys)

        def seq_fwd(stacked, x):
            for s in range(n_stages):
                p = jtu.tree_map(lambda v: v[s], stacked)
                x, _ = stage.apply(p, stage.state, x, training=True)
            return x

        def oracle_loss(stacked):
            outs = jax.vmap(lambda x: seq_fwd(stacked, x))(xs)
            return crit.apply(outs.reshape(-1, d), ys.reshape(-1, d))

        assert abs(float(loss) - float(oracle_loss(stacked))) < 1e-5
        g = jax.grad(oracle_loss)(stacked)
        upd = jtu.tree_map(lambda p, gr: p - 0.1 * gr, stacked, g)
        for a, b in zip(jtu.tree_leaves(new_params), jtu.tree_leaves(upd)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=1e-6)

        # and the loop trains
        params, opt = new_params, new_opt
        losses = []
        for _ in range(8):
            params, opt, loss = step(params, opt, xs, ys)
            losses.append(float(loss))
        assert losses[-1] < losses[0]


class TestMoE:
    def _oracle(self, m, x, k):
        d = x.shape[-1]
        tok = np.asarray(x).reshape(-1, d)
        probs = np.asarray(jax.nn.softmax(
            tok @ np.asarray(m.params["wg"]), axis=-1))
        w1, w2 = np.asarray(m.params["w1"]), np.asarray(m.params["w2"])

        def expert(e, v):
            hh = np.asarray(jax.nn.gelu(v @ w1[e]))
            return hh @ w2[e]

        y_ref = np.zeros_like(tok)
        pr = probs.copy()
        for _ in range(k):
            idx = pr.argmax(-1)
            for i, e in enumerate(idx):
                y_ref[i] += pr[i, e] * expert(e, tok[i])
                pr[i, e] = 0
        return y_ref

    @pytest.mark.slow
    def test_dense_topk_matches_oracle(self):
        d, h, E, k = 16, 32, 8, 2
        m = nn.MoE(d, h, E, k=k, capacity_factor=8.0)  # nothing drops
        m.build(0, (4, 16, d))
        x = jnp.asarray(np.random.default_rng(0)
                        .standard_normal((4, 16, d)), jnp.float32)
        y, st = m.apply(m.params, (), x)
        np.testing.assert_allclose(np.asarray(y).reshape(-1, d),
                                   self._oracle(m, x, k),
                                   rtol=2e-4, atol=1e-5)
        assert float(st["aux_loss"]) > 0
        g = jax.grad(lambda p: jnp.sum(m.apply(p, (), x)[0] ** 2))(m.params)
        assert all(float(jnp.sum(jnp.abs(v))) > 0
                   for v in jtu.tree_leaves(g))

    @pytest.mark.slow
    def test_capacity_drops_tokens(self):
        d, h, E = 8, 16, 2
        m = nn.MoE(d, h, E, k=1, capacity_factor=0.25)
        m.build(0, (1, 16, d))
        x = jnp.asarray(np.random.default_rng(1)
                        .standard_normal((1, 16, d)), jnp.float32)
        y, _ = m.apply(m.params, (), x)
        # over-capacity tokens produce zero output rows
        rows = np.abs(np.asarray(y)[0]).sum(-1)
        assert (rows == 0).any() and (rows > 0).any()

    def test_expert_parallel_matches_dense(self):
        d, h, E, k = 16, 32, 8, 2
        mesh = Mesh(np.asarray(jax.devices()), ("expert",))
        m = nn.MoE(d, h, E, k=k, capacity_factor=8.0)
        m.build(0, (4, 16, d))
        mp = nn.MoE(d, h, E, k=k, capacity_factor=8.0,
                    expert_parallel=("expert", 8))
        x = jnp.asarray(np.random.default_rng(0)
                        .standard_normal((4, 16, d)), jnp.float32)

        def ep_apply(params, xloc):
            yy, _ = mp.apply(params, (), xloc)
            return yy

        from jax import shard_map
        f = jax.jit(shard_map(
            ep_apply, mesh=mesh,
            in_specs=(mp.param_specs(), P("expert")),
            out_specs=P("expert"), check_vma=False))
        y_ep = f(m.params, x.reshape(-1, d))
        np.testing.assert_allclose(np.asarray(y_ep),
                                   self._oracle(m, x, k),
                                   rtol=2e-4, atol=1e-5)
