"""Distributed engine tests on the 8-device virtual CPU mesh.

Reference analog: ``test/.../optim/DistriOptimizerSpec.scala`` ("multi-node
without a cluster", convergence asserts, failure retry) and
``parameters/FP16ParameterSpec`` (wire-codec correctness -> here: sharded
step equals single-device step).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import bigdl_tpu.nn as nn
from bigdl_tpu.optim import SGD, Adam, Trigger, Top1Accuracy, Optimizer
from bigdl_tpu.parallel import DistriOptimizer, make_distributed_train_step
from bigdl_tpu.parallel.allreduce import AllReduceParameter
from bigdl_tpu.dataset import DataSet, SampleToMiniBatch
from bigdl_tpu.dataset.sample import Sample


@pytest.fixture(scope="module")
def mesh():
    devs = np.asarray(jax.devices())
    assert devs.size == 8, "conftest should provide 8 CPU devices"
    return Mesh(devs, axis_names=("data",))


def _model():
    return (nn.Sequential().add(nn.Linear(4, 16)).add(nn.ReLU())
            .add(nn.Linear(16, 3)).add(nn.LogSoftMax()))


def _batch(n=32, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 4)).astype(np.float32)
    y = (np.abs(x).argmax(axis=1) % 3).astype(np.int32)
    return x, y


def _wire_host_model(model, vx, min_margin=1e-4):
    """Host-path twin for exact in-mesh comparisons: same wire-rounded
    (bf16->f32) weights the in-mesh eval all_gathers, so both forwards see
    identical parameters. The top-2 logit margin guard proves the dataset
    has no near-ties within cross-path f32 reduction noise, making argmax
    equality deterministic (de-flake of the old one-sample tolerance)."""
    import copy
    wire_params = jax.tree_util.tree_map(
        lambda v: v.astype(jnp.bfloat16).astype(jnp.float32), model.params)
    host = copy.copy(model)   # __getstate__ strips tensors,
    host.params = wire_params  # so rebind both params and state
    host.state = model.state
    logits, _ = host.apply(wire_params, model.state, jnp.asarray(vx),
                           training=False)
    top2 = np.sort(np.asarray(logits), axis=-1)[:, -2:]
    margin = float(np.min(top2[:, 1] - top2[:, 0]))
    assert margin > min_margin, \
        f"near-tie margin {margin}; pick another seed"
    return host


class TestAllReduceParameter:
    def test_flatten_pad_roundtrip(self):
        model = _model().build(0, (2, 4))
        arp = AllReduceParameter(model.params, 8)
        assert arp.padded_size % 8 == 0
        back = arp.to_params(arp.flat())
        for a, b in zip(jax.tree_util.tree_leaves(back),
                        jax.tree_util.tree_leaves(model.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))


class TestDistributedStep:
    def test_matches_single_device_sgd(self, mesh):
        """The sharded reduce-scatter/update/all-gather step must equal the
        plain single-device step (up to wire-dtype rounding)."""
        model = _model().build(0, (2, 4))
        crit = nn.ClassNLLCriterion()
        x, y = _batch(32)

        # single-device reference step in f32
        def loss_fn(p):
            out, _ = model.apply(p, model.state, jnp.asarray(x), training=True)
            return crit.apply(out, jnp.asarray(y))

        g = jax.grad(loss_fn)(model.params)
        sgd_ref = SGD(learningrate=0.1)
        ref_params, _ = sgd_ref.update(g, sgd_ref.init_state(model.params),
                                       model.params)

        # distributed step in f32 wire to compare exactly
        factory = make_distributed_train_step(
            model, crit, SGD(learningrate=0.1), mesh,
            wire_dtype=jnp.float32)
        step_fn, flat, opt_shard = factory(model.params)
        sharding = NamedSharding(mesh, P("data"))
        xb = jax.device_put(x, sharding)
        yb = jax.device_put(y, sharding)
        new_flat, _, _, loss = step_fn(flat, model.state, opt_shard,
                                       jax.random.key(0), xb, yb)
        arp = AllReduceParameter(model.params, 8)
        dist_params = arp.to_params(new_flat)
        for a, b in zip(jax.tree_util.tree_leaves(dist_params),
                        jax.tree_util.tree_leaves(ref_params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-6)

    def test_opt_state_is_sharded(self, mesh):
        """ZeRO-1: Adam slots must live sharded along the mesh axis."""
        model = _model().build(0, (2, 4))
        factory = make_distributed_train_step(
            model, nn.ClassNLLCriterion(), Adam(), mesh)
        step_fn, flat, opt_shard = factory(model.params)
        m_slot = opt_shard["m"]
        assert m_slot.sharding.spec == P("data")
        arp = AllReduceParameter(model.params, 8)
        assert m_slot.shape == (arp.padded_size,)
        # each device holds 1/8 of the slot, not a replica
        assert m_slot.addressable_shards[0].data.shape == (arp.slice_size,)

    def test_loss_decreases(self, mesh):
        model = _model().build(0, (2, 4))
        crit = nn.ClassNLLCriterion()
        factory = make_distributed_train_step(model, crit,
                                              SGD(learningrate=0.5), mesh)
        step_fn, flat, opt_shard = factory(model.params)
        sharding = NamedSharding(mesh, P("data"))
        x, y = _batch(64)
        xb, yb = jax.device_put(x, sharding), jax.device_put(y, sharding)
        state = model.state
        losses = []
        for i in range(80):
            flat, state, opt_shard, loss = step_fn(flat, state, opt_shard,
                                                   jax.random.key(i), xb, yb)
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.6, losses


class TestDistriOptimizer:
    def test_end_to_end_training(self, mesh):
        model = _model()
        x, y = _batch(256, seed=3)
        samples = [Sample(x[i], y[i]) for i in range(len(x))]
        ds = DataSet.array(samples) >> SampleToMiniBatch(64)
        opt = Optimizer(model=model, dataset=ds,
                        criterion=nn.ClassNLLCriterion(), mesh=mesh)
        assert isinstance(opt, DistriOptimizer)
        opt.set_optim_method(Adam(learningrate=0.02))
        opt.set_end_when(Trigger.max_epoch(15))
        trained = opt.optimize()
        from bigdl_tpu.optim import Evaluator
        res = Evaluator(trained).evaluate(ds, [Top1Accuracy()])
        acc, _ = res["Top1Accuracy"].result()
        assert acc > 0.8, f"accuracy {acc}"

    def test_retry_from_checkpoint(self, tmp_path, mesh):
        """Failure mid-training resumes from the latest checkpoint
        (reference: DistriOptimizerSpec 'failures in small interval')."""
        model = _model()
        x, y = _batch(128, seed=4)
        samples = [Sample(x[i], y[i]) for i in range(len(x))]
        ds = DataSet.array(samples) >> SampleToMiniBatch(32)
        opt = Optimizer(model=model, dataset=ds,
                        criterion=nn.ClassNLLCriterion(), mesh=mesh)
        opt.set_optim_method(SGD(learningrate=0.1))
        opt.set_end_when(Trigger.max_epoch(4))
        opt.set_checkpoint(str(tmp_path), Trigger.several_iteration(2))

        # inject one failure at iteration 5 (reference ExceptionTest layer)
        original = opt._shard_batch
        count = {"n": 0}

        def failing(batch):
            count["n"] += 1
            if count["n"] == 5:
                raise RuntimeError("injected executor failure")
            return original(batch)

        opt._shard_batch = failing
        from bigdl_tpu.visualization import TrainSummary
        ts = TrainSummary(str(tmp_path), "retry")
        opt.set_train_summary(ts)
        trained = opt.optimize()
        assert trained.params is not None
        assert count["n"] > 5  # training continued after the failure
        # post-retry the drain pipeline must track the RELOADED driver
        # state: iteration stamps keep advancing past the failure point
        # and the per-step Loss scalars keep flowing (regression: ahead
        # kept writing into the pre-failure dict)
        steps = [s for s, _ in ts.read_scalar("Loss")]
        assert steps, "no Loss scalars recorded"
        assert max(steps) > 5
        assert len(set(steps)) > 5


class TestGradientAccumulation:
    """accumulate_steps=K: K micro-batches scanned inside ONE jitted step
    — same math as the single big-batch step for mean-reduction criteria,
    one collective pair per step."""

    def test_accumulated_matches_big_batch(self, mesh):
        model = _model().build(0, (2, 4))
        crit = nn.ClassNLLCriterion()
        x, y = _batch(64, seed=9)
        sharding = NamedSharding(mesh, P("data"))
        xb, yb = jax.device_put(x, sharding), jax.device_put(y, sharding)

        results = {}
        for k in (1, 4):
            m = _model().build(0, (2, 4))
            m.params = jax.tree_util.tree_map(jnp.array, model.params)
            factory = make_distributed_train_step(
                m, crit, SGD(learningrate=0.1), mesh,
                wire_dtype=jnp.float32, accumulate_steps=k)
            step_fn, flat, opt_shard = factory(m.params)
            state = m.state
            for i in range(3):
                flat, state, opt_shard, loss = step_fn(
                    flat, state, opt_shard, jax.random.key(i), xb, yb)
            results[k] = (np.asarray(flat), float(loss))

        np.testing.assert_allclose(results[1][0], results[4][0],
                                   rtol=2e-5, atol=1e-6)
        assert abs(results[1][1] - results[4][1]) < 1e-5

    def test_distri_optimizer_accumulates_and_trains(self, mesh):
        model = _model()
        x, y = _batch(256, seed=10)
        samples = [Sample(x[i], y[i]) for i in range(len(x))]
        ds = DataSet.array(samples) >> SampleToMiniBatch(64)
        opt = DistriOptimizer(model=model, dataset=ds,
                              criterion=nn.ClassNLLCriterion(), mesh=mesh,
                              accumulate_steps=4)
        opt.set_optim_method(Adam(learningrate=0.02))
        opt.set_end_when(Trigger.max_epoch(15))
        trained = opt.optimize()
        from bigdl_tpu.optim import Evaluator
        res = Evaluator(trained).evaluate(ds, [Top1Accuracy()])
        acc, _ = res["Top1Accuracy"].result()
        assert acc > 0.8, f"accuracy {acc}"

    def test_indivisible_microbatch_raises(self, mesh):
        model = _model()
        x, y = _batch(64, seed=11)
        samples = [Sample(x[i], y[i]) for i in range(len(x))]
        ds = DataSet.array(samples) >> SampleToMiniBatch(64)
        opt = DistriOptimizer(model=model, dataset=ds,
                              criterion=nn.ClassNLLCriterion(), mesh=mesh,
                              accumulate_steps=3)   # 64/8 = 8 rows; 8 % 3
        opt.set_optim_method(SGD(learningrate=0.1))
        opt.set_end_when(Trigger.max_epoch(1))
        with pytest.raises(ValueError, match="accumulate_steps"):
            opt.optimize()


class TestShardedCheckpoint:
    """BIGDL_TPU_SHARDED_CHECKPOINT=1: gather-free checkpoints — each
    process writes its addressable shards of the f32 master + ZeRO-1
    slots; restore maps blocks back by global offset."""

    def test_sharded_retry_resumes_with_slots(self, tmp_path, mesh,
                                              monkeypatch):
        monkeypatch.setenv("BIGDL_TPU_SHARDED_CHECKPOINT", "1")
        model = _model()
        x, y = _batch(128, seed=6)
        samples = [Sample(x[i], y[i]) for i in range(len(x))]
        ds = DataSet.array(samples) >> SampleToMiniBatch(32)
        opt = DistriOptimizer(model=model, dataset=ds,
                              criterion=nn.ClassNLLCriterion(), mesh=mesh)
        opt.set_optim_method(Adam(learningrate=0.01))  # sharded m/v slots
        opt.set_end_when(Trigger.max_epoch(4))
        opt.set_checkpoint(str(tmp_path), Trigger.several_iteration(2))

        original = opt._shard_batch
        count = {"n": 0}

        def failing(batch):
            count["n"] += 1
            if count["n"] == 6:
                raise RuntimeError("injected executor failure")
            return original(batch)

        opt._shard_batch = failing
        trained = opt.optimize()
        assert trained.params is not None
        assert count["n"] > 6
        import os
        names = sorted(os.listdir(tmp_path))
        assert any(n.startswith("shard.") and n.endswith(".p0")
                   for n in names), names
        assert any(n.startswith("model.") for n in names)

    def test_block_roundtrip_preserves_values(self, mesh, monkeypatch):
        """Save->restore of a sharded array + opt tree is exact."""
        from jax.sharding import NamedSharding
        flat = jnp.arange(64, dtype=jnp.float32)
        sharded = jax.device_put(flat, NamedSharding(mesh, P("data")))
        blocks = DistriOptimizer._local_blocks(sharded)
        assert len(blocks) == 8 and blocks[0][0] == 0
        back = DistriOptimizer._from_blocks(blocks, sharded)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(flat))
        # replicated scalar leaf
        scalar = jax.device_put(jnp.asarray(3, jnp.int32),
                                NamedSharding(mesh, P()))
        blocks = DistriOptimizer._local_blocks(scalar)
        assert blocks[0][0] is None
        back = DistriOptimizer._from_blocks(blocks, scalar)
        assert int(back) == 3

    def test_incomplete_shard_set_raises_not_stale_restore(self, tmp_path,
                                                           mesh):
        """Shard files with no complete set for this layout must fail
        loudly — the gathered model.N twin of a sharded set holds STALE
        params and silently restoring it would restart from init."""
        model = _model()
        x, y = _batch(64, seed=8)
        samples = [Sample(x[i], y[i]) for i in range(len(x))]
        ds = DataSet.array(samples) >> SampleToMiniBatch(32)
        opt = DistriOptimizer(model=model, dataset=ds,
                              criterion=nn.ClassNLLCriterion(), mesh=mesh)
        opt.set_optim_method(SGD(learningrate=0.1))
        opt.checkpoint_path = str(tmp_path)
        # a sharded set written by some other (2-process) layout: this
        # 1-process run can never assemble it
        (tmp_path / "shard.4.p1").write_bytes(b"partial")
        (tmp_path / "model.4").write_bytes(b"stale")
        (tmp_path / "optimMethod.4").write_bytes(b"stale")
        from bigdl_tpu.parallel.allreduce import make_distributed_train_step
        factory = make_distributed_train_step(
            model.build(0, (2, 4)), nn.ClassNLLCriterion(),
            opt.optim_method, mesh)
        with pytest.raises(RuntimeError, match="none is complete"):
            opt._reload_latest(factory)

    def test_shard_group_parsing_skips_tmp(self):
        groups = DistriOptimizer._shard_groups(
            ["shard.2.p0", "shard.2.p1", "shard.4.p0", "shard.4.p1.tmp",
             "model.2", "driverState.2", "shard.bad"])
        assert groups == {2: {0, 1}, 4: {0}}

    def test_wrong_layout_fails_loudly(self, mesh):
        from jax.sharding import NamedSharding
        flat = jnp.arange(64, dtype=jnp.float32)
        sharded = jax.device_put(flat, NamedSharding(mesh, P("data")))
        blocks = DistriOptimizer._local_blocks(sharded)
        shifted = [(s + 4, v) for s, v in blocks if s is not None]
        with pytest.raises(RuntimeError, match="different process/"):
            DistriOptimizer._from_blocks(shifted, sharded)


class TestDispatchAhead:
    """The pipelined loss readout (BIGDL_TPU_DISPATCH_AHEAD) must not
    change the math — only when the host syncs. Reference contract: driver
    loss/throughput bookkeeping per iteration
    (DistriOptimizer.scala:383-451), here stamped with each step's own
    iteration number even though values drain late."""

    def _train(self, mesh, tmp_path, depth, monkeypatch):
        from bigdl_tpu.visualization import TrainSummary
        monkeypatch.setenv("BIGDL_TPU_DISPATCH_AHEAD", str(depth))
        model = _model()
        x, y = _batch(128, seed=5)
        samples = [Sample(x[i], y[i]) for i in range(len(x))]
        ds = DataSet.array(samples) >> SampleToMiniBatch(32)
        ds.shuffle = lambda seed=None: ds   # pin order across the two runs
        opt = DistriOptimizer(model=model, dataset=ds,
                              criterion=nn.ClassNLLCriterion(), mesh=mesh)
        opt.set_optim_method(SGD(learningrate=0.1))
        opt.set_end_when(Trigger.max_epoch(3))
        logdir = str(tmp_path / f"logs{depth}")
        ts = TrainSummary(logdir, f"d{depth}")
        opt.set_train_summary(ts)
        trained = opt.optimize()
        return trained, ts.read_scalar("Loss"), opt

    def test_depths_agree_and_stamp_every_step(self, mesh, tmp_path,
                                               monkeypatch):
        p0, loss0, _ = self._train(mesh, tmp_path, 0, monkeypatch)
        p3, loss3, opt3 = self._train(mesh, tmp_path, 3, monkeypatch)
        # identical math: drain timing must not perturb the weights
        for a, b in zip(jax.tree_util.tree_leaves(p0.params),
                        jax.tree_util.tree_leaves(p3.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # every iteration logged exactly once, in order, same values
        steps0 = [s for s, _ in loss0]
        steps3 = [s for s, _ in loss3]
        assert steps0 == steps3 == list(range(1, len(steps0) + 1))
        np.testing.assert_allclose([v for _, v in loss0],
                                   [v for _, v in loss3], rtol=1e-6)
        # loop accounting intact under pipelining
        m = opt3.metrics_summary()
        assert m["steps"] == len(steps0)
        assert m["throughput_rec_s"] > 0
        assert 0.0 <= m["feed_wait_frac"] <= 1.0


class TestFeedWaitMetric:
    """feed_wait_frac (VERDICT r4 item 5) must actually discriminate a
    feed-bound loop from an overlapped one — not just exist."""

    def _run(self, mesh, transformer_tail):
        import time as _time
        from bigdl_tpu.dataset.transformer import Transformer

        class Slow(Transformer):
            def apply(self, iterator):
                for item in iterator:
                    _time.sleep(0.25)   # decode cost >> tiny step cost
                    yield item

        model = _model()
        x, y = _batch(128, seed=7)
        samples = [Sample(x[i], y[i]) for i in range(len(x))]
        chain = SampleToMiniBatch(32)
        ds = DataSet.array(samples) >> chain
        if transformer_tail == "slow":
            ds = ds >> Slow()
        opt = DistriOptimizer(model=model, dataset=ds,
                              criterion=nn.ClassNLLCriterion(), mesh=mesh)
        opt.set_optim_method(SGD(learningrate=0.1))
        opt.set_end_when(Trigger.max_epoch(3))
        opt.optimize()
        return opt.metrics_summary()["feed_wait_frac"]

    def test_slow_feed_dominates_fast_feed_overlaps(self, mesh):
        # fast first: it pays the one-time jit compile (same shapes), so
        # the slow run's step bucket holds only real step time
        fast = self._run(mesh, "fast")
        slow = self._run(mesh, "slow")
        assert slow > 0.5, f"feed-bound loop reported feed_wait {slow}"
        assert slow > 2 * fast


class TestReviewFixes:
    def test_master_weights_stay_f32_precise(self, mesh):
        """Tiny updates must not be lost to bf16 wire rounding: the f32
        master shard accumulates them (reference keeps f32 weightPartition)."""
        model = nn.Sequential().add(nn.Linear(4, 4, with_bias=False))
        model.build(0, (8, 4))
        crit = nn.MSECriterion()
        factory = make_distributed_train_step(
            model, crit, SGD(learningrate=1e-4), mesh,
            wire_dtype=jnp.bfloat16)
        step_fn, shard, opt_shard = factory(model.params)
        x = jax.device_put(np.ones((8, 4), np.float32),
                           NamedSharding(mesh, P("data")))
        y = jax.device_put(np.zeros((8, 4), np.float32),
                           NamedSharding(mesh, P("data")))
        w0 = np.asarray(jax.device_get(shard))
        state = model.state
        for i in range(50):
            shard, state, opt_shard, _ = step_fn(shard, state, opt_shard,
                                                 jax.random.key(i), x, y)
        w1 = np.asarray(jax.device_get(shard))
        # 50 steps of ~1e-5-sized updates must accumulate (bf16 would eat them)
        assert np.abs(w1 - w0).max() > 1e-4

    def test_freeze_respected_in_distributed(self, mesh):
        model = (nn.Sequential().add(nn.Linear(4, 8)).add(nn.ReLU())
                 .add(nn.Linear(8, 3)).add(nn.LogSoftMax()))
        model.build(0, (8, 4))
        model[0].freeze()
        factory = make_distributed_train_step(
            model, nn.ClassNLLCriterion(), SGD(learningrate=0.5), mesh,
            wire_dtype=jnp.float32)
        step_fn, shard, opt_shard = factory(model.params)
        frozen_before = np.asarray(model.params[0]["weight"]).copy()
        x, y = _batch(32)
        sharding = NamedSharding(mesh, P("data"))
        xb, yb = jax.device_put(x, sharding), jax.device_put(y, sharding)
        state = model.state
        for i in range(5):
            shard, state, opt_shard, _ = step_fn(shard, state, opt_shard,
                                                 jax.random.key(i), xb, yb)
        arp = AllReduceParameter(model.params, 8)
        after = arp.to_params(jax.device_get(shard))
        np.testing.assert_allclose(np.asarray(after[0]["weight"]),
                                   frozen_before)
        assert np.abs(np.asarray(after[2]["weight"])
                      - np.asarray(model.params[2]["weight"])).max() > 1e-4

    def test_eval_masks_padded_tail(self):
        from bigdl_tpu.optim import Evaluator
        from bigdl_tpu.optim.validation import Top1Accuracy
        model = nn.Sequential().add(nn.Linear(4, 3)).add(nn.LogSoftMax())
        model.build(0, (2, 4))
        x, y = _batch(10)  # batch 8 -> tail of 2 padded to 8
        samples = [Sample(x[i], y[i]) for i in range(10)]
        ds = DataSet.array(samples) >> SampleToMiniBatch(8)
        res = Evaluator(model).evaluate(ds, [Top1Accuracy()])
        _, count = res["Top1Accuracy"].result()
        assert count == 10  # not 16

    def test_plateau_reduces_lr_via_opt_state(self):
        from bigdl_tpu.optim.schedules import Plateau
        sched = Plateau(factor=0.1, patience=1, mode="min")
        method = SGD(learningrate=1.0, learningrate_schedule=sched)
        params = {"w": jnp.ones((4,))}
        s = method.init_state(params)
        assert "plateau_mult" in s
        assert float(method.current_lr(s)) == 1.0
        sched.record(1.0)  # best
        sched.record(1.0)  # no improvement #1 -> patience hit -> reduce
        s = {**s, "plateau_mult": jnp.asarray(sched.multiplier, jnp.float32)}
        assert float(method.current_lr(s)) == pytest.approx(0.1)


class TestRecordFilesEndToEnd:
    """The full ImageNet-path shape in miniature: sharded record files ->
    transformer chain -> DistriOptimizer over the 8-device mesh
    (reference: SeqFileFolder ImageNet pipeline + DistriOptimizer)."""

    @pytest.mark.slow
    def test_train_from_shards_over_mesh(self, mesh, tmp_path):
        from bigdl_tpu.dataset.record_file import (RecordFileDataSet,
                                                   write_record_shards)
        from bigdl_tpu.dataset.mnist import synthetic_mnist
        from bigdl_tpu.models.lenet import LeNet5
        from bigdl_tpu.optim import Evaluator, Loss

        images, labels = synthetic_mnist(512, seed=3)
        samples = [Sample((img.astype(np.float32) / 255.0 - 0.1)
                          .reshape(1, 28, 28), np.float32(l))
                   for img, l in zip(images, labels)]
        prefix = str(tmp_path / "mnist")
        write_record_shards(samples, prefix, n_shards=8)

        ds = RecordFileDataSet(prefix, process_index=0, process_count=1)
        ds = ds.transform(SampleToMiniBatch(64))
        model = LeNet5(10)
        opt = DistriOptimizer(model=model, dataset=ds,
                              criterion=nn.ClassNLLCriterion(), mesh=mesh)
        opt.set_optim_method(SGD(learningrate=0.2, momentum=0.9,
                                 dampening=0.0))
        opt.set_end_when(Trigger.max_epoch(10))
        trained = opt.optimize()

        result = Evaluator(trained).evaluate(ds, [Top1Accuracy(), Loss()])
        acc = result["Top1Accuracy"].result()[0]
        assert acc > 0.5, f"accuracy {acc} not above chance"
        assert opt.metrics["steps"] > 0
        assert opt.metrics["allreduce_bytes"] > 0


class TestInMeshValidation:
    def test_validation_in_mesh_matches_host_and_skips_materialize(self,
                                                                   mesh):
        """VERDICT-3 item 4: validation triggers must not materialize the
        weights to host, and the psum'd counters must equal the host-path
        Evaluator result."""
        from bigdl_tpu.optim import Loss
        model = _model()
        x, y = _batch(256, seed=5)
        samples = [Sample(x[i], y[i]) for i in range(len(x))]
        ds = DataSet.array(samples) >> SampleToMiniBatch(64)
        # pin the epoch shuffle: OS-entropy ordering varies the trained
        # weights run-to-run, and once in ~10 runs the result landed
        # inside _wire_host_model's near-tie margin guard (observed
        # margin 2.5e-5 < 1e-4) — deterministic order de-flakes it
        ds.shuffle = lambda seed=None: ds
        # seed 8: top-2 logit margin ~3e-3 after training on this config
        # (seed 6 lands a 6e-6 near-tie on the CPU backend,
        # tripping _wire_host_model's guard)
        vx, vy = _batch(128, seed=8)
        vsamples = [Sample(vx[i], vy[i]) for i in range(len(vx))]
        vds = DataSet.array(vsamples) >> SampleToMiniBatch(64)

        opt = Optimizer(model=model, dataset=ds,
                        criterion=nn.ClassNLLCriterion(), mesh=mesh)
        opt.set_optim_method(SGD(learningrate=0.05))
        opt.set_end_when(Trigger.max_epoch(2))
        opt.set_validation(Trigger.every_epoch(), vds,
                           [Top1Accuracy(), Loss()])

        calls = {"n": 0}
        orig = opt._materialize

        def counting(*a, **kw):
            calls["n"] += 1
            return orig(*a, **kw)

        opt._materialize = counting
        trained = opt.optimize()
        # exactly ONE materialize: the final model collection after
        # optimize(); the two validation triggers used the in-mesh path
        assert calls["n"] == 1, f"materialize called {calls['n']} times"
        assert opt._eval_fn is not None

        # EXACT equality with the host path (see _wire_host_model)
        from bigdl_tpu.optim import Evaluator
        host_model = _wire_host_model(trained, vx)
        host = Evaluator(host_model).evaluate(vds, [Top1Accuracy(), Loss()])
        host_acc, host_n = host["Top1Accuracy"].result()

        flat = AllReduceParameter(trained.params, 8).flat()
        from jax.sharding import NamedSharding
        flat = jax.device_put(flat, NamedSharding(mesh, P("data")))
        state = jax.device_put(trained.state, NamedSharding(mesh, P()))
        res = opt._validate_inmesh(flat, state)
        acc, n = res["Top1Accuracy"].result()
        assert n == host_n
        assert acc == host_acc, (acc, host_acc)
        lh, _ = host["Loss"].result()
        lm, _ = res["Loss"].result()
        assert abs(lh - lm) < 1e-5, (lh, lm)

    def test_padded_tail_masked_exactly(self, mesh):
        """VERDICT r3 item 3: dataset size % batch != 0 — the padded tail
        batch is masked inside the eval step (not skipped), so the in-mesh
        result equals the host-path result exactly, counting every real
        sample once (reference ``optim/DistriValidator.scala:25``)."""
        from bigdl_tpu.optim import Evaluator, Loss

        model = _model().build(0, (2, 4))
        # 100 % 64 != 0 -> second batch is 36 real rows padded to 64
        vx, vy = _batch(100, seed=11)
        vsamples = [Sample(vx[i], vy[i]) for i in range(len(vx))]
        vds = DataSet.array(vsamples) >> SampleToMiniBatch(64)

        opt = Optimizer(model=model, dataset=vds,
                        criterion=nn.ClassNLLCriterion(), mesh=mesh)
        opt.set_optim_method(SGD(learningrate=0.05))
        opt.set_validation(Trigger.every_epoch(), vds,
                           [Top1Accuracy(), Loss()])

        host_model = _wire_host_model(model, vx)
        host = Evaluator(host_model).evaluate(vds, [Top1Accuracy(), Loss()])
        host_acc, host_n = host["Top1Accuracy"].result()
        assert host_n == 100  # the host path counts every real sample

        flat = AllReduceParameter(model.params, 8).flat()
        flat = jax.device_put(flat, NamedSharding(mesh, P("data")))
        state = jax.device_put(model.state, NamedSharding(mesh, P()))
        res = opt._validate_inmesh(flat, state)
        acc, n = res["Top1Accuracy"].result()
        assert n == 100, f"in-mesh counted {n} of 100 samples"
        assert acc == host_acc, (acc, host_acc)
        lh, _ = host["Loss"].result()
        lm, ln = res["Loss"].result()
        assert ln == 100
        assert abs(lh - lm) < 1e-5, (lh, lm)

    def test_custom_method_falls_back_to_host(self, mesh):
        from bigdl_tpu.optim.validation import (ValidationMethod,
                                                AccuracyResult)

        class Weird(ValidationMethod):
            name = "Weird"

            def __call__(self, output, target):
                return AccuracyResult(1, 1)

        model = _model()
        x, y = _batch(64, seed=7)
        samples = [Sample(x[i], y[i]) for i in range(len(x))]
        ds = DataSet.array(samples) >> SampleToMiniBatch(32)
        opt = Optimizer(model=model, dataset=ds,
                        criterion=nn.ClassNLLCriterion(), mesh=mesh)
        opt.set_optim_method(SGD(learningrate=0.05))
        opt.set_end_when(Trigger.max_epoch(1))
        opt.set_validation(Trigger.every_epoch(), ds, [Weird()])
        trained = opt.optimize()
        assert trained is not None  # host fallback keeps custom methods live


class TestDistriPredictor:
    def test_sharded_predict_matches_host(self, mesh):
        from bigdl_tpu.optim import DistriPredictor, Predictor
        model = _model()
        model.build(0, (8,) + _batch(8)[0].shape[1:])
        x, y = _batch(64, seed=9)
        samples = [Sample(x[i], y[i]) for i in range(len(x))]
        ds = DataSet.array(samples) >> SampleToMiniBatch(16)
        host = Predictor(model).predict(ds)
        sharded = DistriPredictor(model, mesh=mesh).predict(ds)
        np.testing.assert_allclose(np.asarray(sharded), np.asarray(host),
                                   rtol=1e-5, atol=1e-6)

    def test_indivisible_tail_falls_back(self, mesh):
        from bigdl_tpu.optim import DistriPredictor, Predictor
        model = _model()
        model.build(0, (8,) + _batch(8)[0].shape[1:])
        x, y = _batch(15, seed=10)
        samples = [Sample(x[i], y[i]) for i in range(len(x))]
        # batch size 5: every batch is indivisible by the 8-device mesh, so
        # the replicated fallback path runs; output aligns 1:1 with samples
        ds = DataSet.array(samples) >> SampleToMiniBatch(5)
        out = DistriPredictor(model, mesh=mesh).predict(ds)
        assert out.shape[0] == 15
        host = Predictor(model).predict(ds)
        np.testing.assert_allclose(np.asarray(out), np.asarray(host),
                                   rtol=1e-5, atol=1e-6)

    def test_padded_tail_trimmed(self, mesh):
        # 19 samples, batch 8 -> padded tail; predictions must be 19 rows
        from bigdl_tpu.optim import DistriPredictor, Predictor
        model = _model()
        model.build(0, (8,) + _batch(8)[0].shape[1:])
        x, y = _batch(19, seed=11)
        samples = [Sample(x[i], y[i]) for i in range(len(x))]
        ds = DataSet.array(samples) >> SampleToMiniBatch(8)
        assert Predictor(model).predict(ds).shape[0] == 19
        assert DistriPredictor(model, mesh=mesh).predict(ds).shape[0] == 19


class TestAsyncCheckpoint:
    def test_async_checkpoint_files_complete(self, tmp_path, mesh):
        model = _model()
        x, y = _batch(128, seed=12)
        samples = [Sample(x[i], y[i]) for i in range(len(x))]
        ds = DataSet.array(samples) >> SampleToMiniBatch(32)
        opt = Optimizer(model=model, dataset=ds,
                        criterion=nn.ClassNLLCriterion(), mesh=mesh)
        opt.set_optim_method(SGD(learningrate=0.1))
        opt.set_end_when(Trigger.max_epoch(3))
        opt.set_checkpoint(str(tmp_path), Trigger.several_iteration(2))
        trained = opt.optimize()
        # optimize() joined the writer: every trigger's files are on disk
        import os
        models = sorted(f for f in os.listdir(tmp_path)
                        if f.startswith("model."))
        assert models, "no checkpoints written"
        from bigdl_tpu.utils.serializer import load_module
        latest = max(models, key=lambda f: int(f.split(".")[1]))
        loaded = load_module(str(tmp_path / latest))
        assert loaded.params is not None

    def test_sync_flag_restores_blocking_write(self, tmp_path, mesh,
                                               monkeypatch):
        monkeypatch.setenv("BIGDL_TPU_ASYNC_CHECKPOINT", "0")
        model = _model()
        x, y = _batch(64, seed=13)
        samples = [Sample(x[i], y[i]) for i in range(len(x))]
        ds = DataSet.array(samples) >> SampleToMiniBatch(32)
        opt = Optimizer(model=model, dataset=ds,
                        criterion=nn.ClassNLLCriterion(), mesh=mesh)
        opt.set_optim_method(SGD(learningrate=0.1))
        opt.set_end_when(Trigger.max_epoch(1))
        opt.set_checkpoint(str(tmp_path), Trigger.several_iteration(1))
        opt.optimize()
        assert getattr(opt, "_ckpt_thread", None) is None
        import os
        assert any(f.startswith("model.") for f in os.listdir(tmp_path))


class TestAllreduceBandwidth:
    def test_step_pattern_and_psum(self, mesh, monkeypatch):
        """VERDICT r3 item 5: the efficiency metric times the train step's
        actual collective pair (all_gather weights + psum_scatter grads),
        not just the psum primitive (reference optim/Metrics.scala:103)."""
        from bigdl_tpu.parallel import allreduce_bandwidth
        monkeypatch.delenv("BIGDL_TPU_PEAK_ICI_GBPS", raising=False)
        step = allreduce_bandwidth(mesh, size_mb=2, iters=3)
        assert step["pattern"] == "all_gather+psum_scatter (train step)"
        assert step["bus_bandwidth_gbps"] > 0
        psum = allreduce_bandwidth(mesh, size_mb=2, iters=3, pattern="psum")
        assert psum["pattern"] == "psum"
        assert psum["bus_bandwidth_gbps"] > 0
        # CPU mesh has no ICI table entry -> efficiency omitted, not faked
        assert "efficiency_vs_peak" not in step

    def test_efficiency_pipeline_with_peak_override(self, mesh, monkeypatch):
        """VERDICT r4 item 6: the full efficiency pipeline — peak lookup ->
        efficiency field — exercised end to end with the denominator
        PRESENT (BIGDL_TPU_PEAK_ICI_GBPS override), the configuration a
        real ICI run uses (BASELINE.json north star: >=90% on ICI)."""
        from bigdl_tpu.parallel import allreduce_bandwidth
        from bigdl_tpu.parallel.allreduce import ici_peak_gbps
        monkeypatch.setenv("BIGDL_TPU_PEAK_ICI_GBPS", "50")
        assert ici_peak_gbps() == 50.0
        step = allreduce_bandwidth(mesh, size_mb=2, iters=3)
        assert step["ici_peak_gbps"] == 50.0
        assert step["efficiency_vs_peak"] == pytest.approx(
            step["bus_bandwidth_gbps"] / 50.0)
        assert step["efficiency_vs_peak"] > 0

    def test_peak_table_by_device_kind(self, monkeypatch):
        """The generation table resolves without a live TPU backend."""
        from bigdl_tpu.parallel.allreduce import ici_peak_gbps
        monkeypatch.delenv("BIGDL_TPU_PEAK_ICI_GBPS", raising=False)
        assert ici_peak_gbps("TPU v5 lite") == 50.0
        assert ici_peak_gbps("TPU v4") == 100.0
        assert ici_peak_gbps("TPU v5p") == 100.0
        assert ici_peak_gbps("weird accelerator") is None


class TestCheckpointCrashRecovery:
    """Resume selection must survive the write sequence dying half-way:
    model.N and optimMethod.N land as two separate atomic renames, so a
    crash between them (or mid-swap, leaving model.N.tmp) produces a
    directory where N looks newest but is not restorable."""

    def _setup(self, tmp_path, mesh, seed):
        model = _model()
        x, y = _batch(64, seed=seed)
        samples = [Sample(x[i], y[i]) for i in range(len(x))]
        ds = DataSet.array(samples) >> SampleToMiniBatch(32)
        opt = DistriOptimizer(model=model, dataset=ds,
                              criterion=nn.ClassNLLCriterion(), mesh=mesh)
        opt.set_optim_method(SGD(learningrate=0.1))
        opt.checkpoint_path = str(tmp_path)
        model.build(0, (2, 4))
        factory = make_distributed_train_step(
            model, nn.ClassNLLCriterion(), opt.optim_method, mesh)
        return model, opt, factory

    def test_crash_between_renames_falls_back(self, tmp_path, mesh):
        """model.4 landed, optimMethod.4 did not, and the killed swap left
        model.4.tmp — _reload_latest must pick the complete neval=2
        snapshot instead of raising mid-restore (or, worse, parsing
        'model.4.tmp' as a candidate)."""
        from bigdl_tpu.utils.serializer import save_module
        model, opt, factory = self._setup(tmp_path, mesh, seed=9)
        opt._write_model_and_method(2, model, None)   # complete snapshot
        good = jax.tree_util.tree_map(np.asarray, model.params)
        # the crashed, newer, incomplete snapshot carries DIFFERENT params
        # so a wrong pick is observable
        model.params = jax.tree_util.tree_map(lambda v: v + 1.0,
                                              model.params)
        save_module(model, str(tmp_path / "model.4"))
        (tmp_path / "model.4.tmp").write_bytes(b"partial")
        flat_w, _, _, driver_state = opt._reload_latest(factory)
        assert driver_state["neval"] == 2
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a), b),
            opt.model.params, good)

    def test_unparseable_names_are_skipped(self, tmp_path, mesh):
        """Files like model.backup must not blow up the int() parse."""
        model, opt, factory = self._setup(tmp_path, mesh, seed=10)
        opt._write_model_and_method(3, model, None)
        (tmp_path / "model.backup").write_bytes(b"junk")
        (tmp_path / "model.").write_bytes(b"junk")
        _, _, _, driver_state = opt._reload_latest(factory)
        assert driver_state["neval"] == 3

    def test_no_restorable_snapshot_still_raises(self, tmp_path, mesh):
        model, opt, factory = self._setup(tmp_path, mesh, seed=11)
        (tmp_path / "model.4.tmp").write_bytes(b"partial")
        (tmp_path / "model.5").write_bytes(b"no twin")  # optimMethod gone
        with pytest.raises(RuntimeError, match="no checkpoint"):
            opt._reload_latest(factory)


class TestShardedMarker:
    """model.N written under BIGDL_TPU_SHARDED_CHECKPOINT is topology-only
    (stale params); the embedded marker keeps load_module from handing it
    out as a trained model once its shard set is gone."""

    def test_refuses_without_shards_loads_with(self, tmp_path):
        from bigdl_tpu.utils.serializer import load_module, save_module
        model = _model()
        model.build(0, (2, 4))
        model._sharded_weights_marker = {"neval": 3, "nprocs": 2}
        save_module(model, str(tmp_path / "model.3"))
        with pytest.raises(ValueError, match="STALE placeholder"):
            load_module(str(tmp_path / "model.3"))
        (tmp_path / "shard.3.p0").write_bytes(b"x")
        (tmp_path / "shard.3.p1").write_bytes(b"x")
        loaded = load_module(str(tmp_path / "model.3"))
        assert loaded._sharded_weights_marker == {"neval": 3, "nprocs": 2}
        # a leftover .tmp shard alone does not count as "shards present"
        (tmp_path / "shard.3.p0").unlink()
        (tmp_path / "shard.3.p1").unlink()
        (tmp_path / "shard.3.p0.tmp").write_bytes(b"x")
        with pytest.raises(ValueError, match="STALE placeholder"):
            load_module(str(tmp_path / "model.3"))

    def test_optimize_writes_marker(self, tmp_path, mesh, monkeypatch):
        """The real sharded checkpoint path stamps the marker."""
        import os
        from bigdl_tpu.utils.serializer import load_module
        monkeypatch.setenv("BIGDL_TPU_SHARDED_CHECKPOINT", "1")
        model = _model()
        x, y = _batch(64, seed=12)
        samples = [Sample(x[i], y[i]) for i in range(len(x))]
        ds = DataSet.array(samples) >> SampleToMiniBatch(32)
        opt = DistriOptimizer(model=model, dataset=ds,
                              criterion=nn.ClassNLLCriterion(), mesh=mesh)
        opt.set_optim_method(SGD(learningrate=0.1))
        opt.set_end_when(Trigger.max_epoch(2))
        opt.set_checkpoint(str(tmp_path), Trigger.several_iteration(2))
        opt.optimize()
        nevals = sorted(int(n.split(".")[1]) for n in os.listdir(tmp_path)
                        if n.startswith("model.") and ".tmp" not in n)
        assert nevals
        loaded = load_module(str(tmp_path / f"model.{nevals[-1]}"))
        assert loaded._sharded_weights_marker["neval"] == nevals[-1]
        assert loaded._sharded_weights_marker["nprocs"] == 1


class TestHookDrainsDispatchAhead:
    def test_driver_state_loss_current_at_checkpoint(self, tmp_path, mesh,
                                                     monkeypatch):
        """_save_driver_state must persist the loss of the step that just
        ran, not one lagging `depth` dispatches behind (the hooks drain
        the pipelined readout before reading driver_state)."""
        import pickle
        from bigdl_tpu.visualization import TrainSummary
        monkeypatch.setenv("BIGDL_TPU_DISPATCH_AHEAD", "3")
        model = _model()
        x, y = _batch(128, seed=13)
        samples = [Sample(x[i], y[i]) for i in range(len(x))]
        ds = DataSet.array(samples) >> SampleToMiniBatch(32)
        opt = DistriOptimizer(model=model, dataset=ds,
                              criterion=nn.ClassNLLCriterion(), mesh=mesh)
        opt.set_optim_method(SGD(learningrate=0.05))
        opt.set_end_when(Trigger.max_epoch(3))
        opt.set_checkpoint(str(tmp_path), Trigger.several_iteration(3))
        ts = TrainSummary(str(tmp_path), "drain")
        opt.set_train_summary(ts)
        opt.optimize()
        losses = dict(ts.read_scalar("Loss"))
        checked = 0
        import os
        for name in os.listdir(tmp_path):
            if (name.startswith("driverState.")
                    and name != "driverState.latest"):
                with open(tmp_path / name, "rb") as f:
                    st = pickle.load(f)
                # hooks see neval already advanced past the step whose
                # loss the drain just published
                assert st["loss"] == pytest.approx(losses[st["neval"] - 1])
                checked += 1
        assert checked > 0
