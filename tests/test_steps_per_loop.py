"""steps_per_loop fused-training-loop tests.

The contract under test (optim/optimizer.make_train_loop and the
superbatch drivers): K full optimizer steps scanned inside ONE jitted
dispatch must be observably identical to the classic per-step loop —
same loss trajectory, same final params, same trigger firing steps and
checkpoint sets — while the dispatch count drops to ~steps/K.
"""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.dataset import (DataSet, DeviceFeed, SampleToMiniBatch,
                               SuperBatch, ToSuperBatch)
from bigdl_tpu.dataset.minibatch import MiniBatch
from bigdl_tpu.dataset.sample import Sample
from bigdl_tpu.optim import (Adam, SGD, Loss, LocalOptimizer, Optimizer,
                             Top1Accuracy, Trigger)


class CaptureSummary:
    """Minimal TrainSummary stand-in recording per-step scalars."""

    def __init__(self):
        self.scalars = {}
        self._summary_trigger = {}

    def add_scalar(self, name, value, step):
        self.scalars.setdefault(name, {})[step] = value

    def add_histogram(self, *args, **kwargs):
        pass


def _xor_ds(n=160, batch=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 2)).astype(np.float32)
    y = ((x[:, 0] > 0) ^ (x[:, 1] > 0)).astype(np.int32)
    samples = [Sample(x[i], y[i]) for i in range(n)]
    ds = DataSet.array(samples) >> SampleToMiniBatch(batch)
    ds.shuffle = lambda *a, **kw: ds   # pin data order for parity runs
    return ds


def _mlp(din=2, dout=2):
    return (nn.Sequential().add(nn.Linear(din, 16)).add(nn.ReLU())
            .add(nn.Linear(16, dout)).add(nn.LogSoftMax()))


def _run_local(k, accumulate=1, epochs=2, n=160, batch=16,
               configure=None):
    """Train the XOR MLP; returns (loss-by-step, params, metrics, opt)."""
    opt = Optimizer(model=_mlp(), dataset=_xor_ds(n, batch),
                    criterion=nn.ClassNLLCriterion(),
                    steps_per_loop=k, accumulate_steps=accumulate)
    assert isinstance(opt, LocalOptimizer)
    opt.set_optim_method(Adam(learningrate=0.01))
    opt.set_end_when(Trigger.max_epoch(epochs))
    summ = CaptureSummary()
    opt.set_train_summary(summ)
    if configure is not None:
        configure(opt)
    trained = opt.optimize()
    return summ.scalars["Loss"], trained.params, opt.metrics, opt


class TestSuperBatchUnits:
    def test_from_minibatches_stacks_and_sizes(self):
        bs = [MiniBatch(np.full((4, 3), i, np.float32),
                        np.full((4,), i, np.int32),
                        real_size=4 - (i == 2))
              for i in range(3)]
        sb = SuperBatch.from_minibatches(bs)
        assert sb.k == 3
        assert sb.input.shape == (3, 4, 3)
        assert sb.target.shape == (3, 4)
        assert sb.sizes == [4, 4, 4]
        assert sb.real_sizes == [4, 4, 3]
        assert sb.size() == 12

    def test_mismatched_shapes_raise(self):
        bs = [MiniBatch(np.zeros((4, 3), np.float32)),
              MiniBatch(np.zeros((2, 3), np.float32))]
        with pytest.raises(ValueError, match="uniformly-shaped"):
            SuperBatch.from_minibatches(bs)

    def test_slice_steps(self):
        bs = [MiniBatch(np.full((2, 1), i, np.float32),
                        np.full((2,), i, np.int32)) for i in range(4)]
        sb = SuperBatch.from_minibatches(bs).slice_steps(1, 3)
        assert sb.k == 2
        np.testing.assert_array_equal(sb.input[:, 0, 0], [1.0, 2.0])
        assert sb.sizes == [2, 2]

    def test_to_superbatch_groups_and_truncated_tail(self):
        batches = [MiniBatch(np.full((2, 1), i, np.float32),
                             np.full((2,), i, np.int32)) for i in range(10)]
        ks = [sb.k for sb in ToSuperBatch(8)(iter(batches))]
        assert ks == [8, 2]
        with pytest.raises(ValueError, match="positive integer"):
            ToSuperBatch(0)

    def test_device_feed_order_and_lookahead(self):
        events = []

        def gen():
            for i in range(4):
                events.append(("gen", i))
                yield i

        out = list(DeviceFeed(lambda i: ("put", i))(gen()))
        assert out == [(i, ("put", i)) for i in range(4)]
        # double-buffering: item 1's transfer is issued BEFORE item 0 is
        # handed to the consumer
        assert events == [("gen", 0), ("gen", 1), ("gen", 2), ("gen", 3)]

        events2 = []

        def gen2():
            for i in range(3):
                yield i

        feed = DeviceFeed(lambda i: events2.append(("put", i)) or i)(gen2())
        first = next(feed)
        # consuming the first item required put(0) AND the lookahead put(1)
        assert events2 == [("put", 0), ("put", 1)]
        assert first[0] == 0


class TestLocalParity:
    def test_k8_matches_k1_losses_and_params(self):
        # 160/16 = 10 steps/epoch: K=8 exercises a full superbatch AND the
        # truncated 2-step epoch tail every epoch
        l1, p1, m1, _ = _run_local(1)
        l8, p8, m8, _ = _run_local(8)
        assert m1["steps"] == m8["steps"] == 20
        assert set(l1) == set(l8)
        for s in l1:
            assert abs(l1[s] - l8[s]) < 1e-5, (s, l1[s], l8[s])
        for a, b in zip(jax.tree_util.tree_leaves(p1),
                        jax.tree_util.tree_leaves(p8)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=1e-6)

    def test_k8_matches_k1_with_accumulate(self):
        l1, p1, _, _ = _run_local(1, accumulate=4, epochs=1)
        l8, p8, _, _ = _run_local(8, accumulate=4, epochs=1)
        assert set(l1) == set(l8)
        for s in l1:
            assert abs(l1[s] - l8[s]) < 1e-5
        for a, b in zip(jax.tree_util.tree_leaves(p1),
                        jax.tree_util.tree_leaves(p8)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=1e-6)


class TestDispatchCount:
    def test_k_steps_cost_one_dispatch(self, monkeypatch):
        """The acceptance bound: N steps at steps_per_loop=8 take at most
        ceil(N/8)+1 jitted train dispatches, counted both by the driver
        metric and by wrapping the fused loop itself."""
        import bigdl_tpu.optim.optimizer as om
        calls = {"n": 0}
        real = om.make_train_loop

        def counting_make(*args, **kwargs):
            loop = real(*args, **kwargs)

            def wrapped(*a, **kw):
                calls["n"] += 1
                return loop(*a, **kw)

            return wrapped

        monkeypatch.setattr(om, "make_train_loop", counting_make)
        _, _, m, _ = _run_local(8, n=128, epochs=2)   # N = 16 steps
        assert m["steps"] == 16
        assert calls["n"] == m["dispatches"]
        assert m["dispatches"] <= math.ceil(16 / 8) + 1

    def test_k1_dispatch_per_step(self):
        _, _, m, _ = _run_local(1, n=128, epochs=1)
        assert m["dispatches"] == m["steps"] == 8


class TestTriggerSemantics:
    def test_checkpoint_sets_match_k1(self, tmp_path):
        """several_iteration(3) falls mid-superbatch at K=8: the scan must
        truncate at the boundary and write the exact checkpoint set the
        K=1 loop writes."""
        sets = {}
        for k in (1, 8):
            path = tmp_path / f"k{k}"
            _run_local(k, epochs=1, configure=lambda o: o.set_checkpoint(
                str(path), Trigger.several_iteration(3)))
            sets[k] = {f for f in os.listdir(path)
                       if f.startswith("model.")}
        assert sets[8] == sets[1]
        assert sets[1] == {"model.3", "model.6", "model.9"}

    def test_validation_steps_match_k1(self):
        steps = {}
        for k in (1, 8):
            vsum = CaptureSummary()

            def configure(o, vs=vsum):
                o.set_validation(Trigger.several_iteration(4), _xor_ds(64),
                                 [Top1Accuracy(), Loss()])
                o.set_validation_summary(vs)

            _run_local(k, epochs=1, configure=configure)
            steps[k] = set(vsum.scalars["Top1Accuracy"])
        assert steps[8] == steps[1]
        assert steps[1]   # it actually fired

    def test_max_iteration_truncates_exactly(self):
        """end_when mid-superbatch: exactly N steps run, not a full K."""
        l, _, m, _ = _run_local(
            8, configure=lambda o: o.set_end_when(Trigger.max_iteration(5)))
        assert m["steps"] == 5
        assert set(l) == {1, 2, 3, 4, 5}
        # 5 steps split at the end_when boundary: 5 = one truncated scan
        # (plan stops at j=5) -> 1 dispatch
        assert m["dispatches"] <= 2


class TestFlagAndValidation:
    def test_invalid_steps_per_loop_raises(self):
        with pytest.raises(ValueError, match="positive integer"):
            Optimizer(model=_mlp(), dataset=_xor_ds(),
                      criterion=nn.ClassNLLCriterion(), steps_per_loop=0)

    def test_env_flag_is_the_default(self, monkeypatch):
        monkeypatch.setenv("BIGDL_TPU_STEPS_PER_LOOP", "4")
        opt = Optimizer(model=_mlp(), dataset=_xor_ds(),
                        criterion=nn.ClassNLLCriterion())
        assert opt.steps_per_loop == 4
        # explicit kwarg wins over the env default
        opt = Optimizer(model=_mlp(), dataset=_xor_ds(),
                        criterion=nn.ClassNLLCriterion(), steps_per_loop=2)
        assert opt.steps_per_loop == 2


class TestDistriParity:
    @pytest.fixture(scope="class")
    def mesh(self):
        from jax.sharding import Mesh
        devs = np.asarray(jax.devices())
        assert devs.size == 8, "conftest should provide 8 CPU devices"
        return Mesh(devs, axis_names=("data",))

    def _run(self, mesh, k, epochs=1):
        from bigdl_tpu.parallel import DistriOptimizer
        rng = np.random.default_rng(0)
        x = rng.standard_normal((128, 4)).astype(np.float32)
        y = (np.abs(x).argmax(axis=1) % 3).astype(np.int32)
        samples = [Sample(x[i], y[i]) for i in range(len(x))]
        ds = DataSet.array(samples) >> SampleToMiniBatch(16)
        ds.shuffle = lambda *a, **kw: ds
        opt = DistriOptimizer(model=_mlp(4, 3), dataset=ds,
                              criterion=nn.ClassNLLCriterion(), mesh=mesh,
                              steps_per_loop=k)
        opt.set_optim_method(Adam(learningrate=0.01))
        opt.set_end_when(Trigger.max_epoch(epochs))
        summ = CaptureSummary()
        opt.set_train_summary(summ)
        trained = opt.optimize()
        return summ.scalars["Loss"], trained.params, opt.metrics

    def test_k4_matches_k1(self, mesh):
        l1, p1, m1 = self._run(mesh, 1)
        l4, p4, m4 = self._run(mesh, 4)
        assert m1["steps"] == m4["steps"] == 8
        assert m4["dispatches"] == 2
        assert m4["allreduce_bytes"] == m1["allreduce_bytes"]
        assert set(l1) == set(l4)
        for s in l1:
            assert abs(l1[s] - l4[s]) < 1e-5, (s, l1[s], l4[s])
        for a, b in zip(jax.tree_util.tree_leaves(p1),
                        jax.tree_util.tree_leaves(p4)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=1e-6)
