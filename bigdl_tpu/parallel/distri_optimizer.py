"""DistriOptimizer: synchronous data-parallel training over the device mesh.

Reference: ``optim/DistriOptimizer.scala`` — driver loop running 2 Spark jobs
per iteration (compute+putGradients, then aggregate+update+sendWeights) with
straggler dropping and retry-from-checkpoint. TPU-natively one iteration is
ONE jitted XLA program (see parallel/allreduce.py); this class is the driver:
epochs, shuffling, per-host input feeding, triggers, validation, checkpoint,
metrics, and the retry loop.

Differences by design (SURVEY.md section 5):
- straggler dropping is a no-op knob: ICI collectives are synchronous; the
  ``drop_percentage`` argument is accepted and ignored for API parity.
- failure recovery: synchronous TPU collectives fail collectively, so the
  retry loop reloads the latest checkpoint and rebuilds the jitted step
  (reference: ``DistriOptimizer.scala:907-976`` reload + rebuild models RDD).
"""

from __future__ import annotations

import logging
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bigdl_tpu import obs
from bigdl_tpu.nn.module import tree_zeros_like
from bigdl_tpu.optim.optimizer import Optimizer, _split_chain
from bigdl_tpu.parallel.allreduce import (make_distributed_train_step,
                                          record_allreduce)
from bigdl_tpu.resilience.faults import fault_point
from bigdl_tpu.resilience.preempt import TrainingPreempted

logger = logging.getLogger("bigdl_tpu.parallel")


class DistriOptimizer(Optimizer):
    def __init__(self, model=None, dataset=None, criterion=None, mesh=None,
                 axis="data", wire_dtype=None, compute_dtype=None,
                 drop_percentage=0.0, failure_retry_times=None,
                 accumulate_steps=1, **kwargs):
        # validated + stored by the base (K micro-batches per jitted step;
        # see allreduce.make_distributed_train_step)
        super().__init__(model, dataset, criterion,
                         accumulate_steps=accumulate_steps, **kwargs)
        from bigdl_tpu.utils.engine import Engine, get_flag
        self.mesh = mesh if mesh is not None else Engine.mesh()
        self.axis = axis
        self.wire_dtype = wire_dtype or jnp.bfloat16
        self.compute_dtype = compute_dtype
        self.drop_percentage = drop_percentage  # accepted, no-op on TPU
        if failure_retry_times is None:
            failure_retry_times = get_flag("BIGDL_TPU_FAILURE_RETRY_TIMES",
                                           5, int)
        self.failure_retry_times = failure_retry_times
        # failures further apart than this window don't accumulate toward the
        # budget (reference: bigdl.failure.retryTimeInterval, 120 s)
        self.failure_retry_interval = get_flag(
            "BIGDL_TPU_FAILURE_RETRY_INTERVAL", 120.0, float)
        # per-iteration phase accumulators (reference: optim/Metrics.scala:31
        # populated at DistriOptimizer.scala:184-192). One jitted step fuses
        # compute+collectives, so the phases a host can see are data feed vs
        # device step; wire traffic is computed analytically from the
        # collective pattern (all_gather + psum_scatter per step).
        # "dispatches" counts jitted train invocations — steps at K=1,
        # ~steps/steps_per_loop in fused-loop mode.
        self.metrics = {"allreduce_bytes": 0, "steps": 0,
                        "data_time": 0.0, "step_time": 0.0,
                        "records": 0, "dispatches": 0}
        self._eval_fn = None  # lazily-built in-mesh validation step

    # clipping stored as a spec tuple (see allreduce.py)
    def set_gradient_clipping_by_l2_norm(self, max_norm):
        self.clipping = ("l2norm", max_norm)
        return self

    def set_constant_gradient_clipping(self, min_value, max_value):
        self.clipping = ("constant", min_value, max_value)
        return self

    def _shard_valid(self, size, real):
        """Per-sample validity mask, sharded exactly like the batch rows
        (incl. the multi-host assembly path `_shard_batch` uses). Cached:
        every batch except the epoch's tail shares the all-True mask."""
        cache = getattr(self, "_valid_cache", None)
        if cache is None:
            cache = self._valid_cache = {}
        key = (size, real)
        if key not in cache:
            mask = np.arange(size) < real
            sharding = NamedSharding(self.mesh, P(self.axis))
            cache[key] = (
                jax.make_array_from_process_local_data(sharding, mask)
                if jax.process_count() > 1
                else jax.device_put(mask, sharding))
        return cache[key]

    def _shard_batch(self, batch):
        x = np.asarray(batch.get_input())
        y = np.asarray(batch.get_target())
        ndev = self.mesh.shape[self.axis]
        sharding = NamedSharding(self.mesh, P(self.axis))
        if jax.process_count() > 1:
            # each host feeds its local shard of the global batch (the
            # reference's per-executor partition of the RDD batch); jax
            # assembles the global array across hosts
            if (x.shape[0] * jax.process_count()) % ndev:
                raise ValueError(
                    f"local batch {x.shape[0]} x {jax.process_count()} hosts "
                    f"must divide the mesh's '{self.axis}' axis ({ndev})")
            k = self.accumulate_steps
            rows = x.shape[0] * jax.process_count() // ndev
            if k > 1 and rows % k:
                raise ValueError(
                    f"accumulate_steps={k} must divide the per-device "
                    f"batch rows ({rows}); keep SampleToMiniBatch's default "
                    "pad_last=True, or set drop_last=True")
            return (jax.make_array_from_process_local_data(sharding, x),
                    jax.make_array_from_process_local_data(sharding, y))
        if x.shape[0] % ndev:
            raise ValueError(
                f"batch size {x.shape[0]} must be divisible by the mesh's "
                f"'{self.axis}' axis size {ndev} (reference requirement: "
                "batchSize % nodeNumber == 0, Optimizer.scala)")
        k = self.accumulate_steps
        if k > 1 and (x.shape[0] // ndev) % k:
            # checked per batch: a variable-size tail would otherwise die
            # inside the jitted micro-batch reshape with a trace error
            raise ValueError(
                f"accumulate_steps={k} must divide the per-device batch "
                f"rows ({x.shape[0] // ndev}); keep SampleToMiniBatch's "
                "default pad_last=True, or set drop_last=True")
        return (jax.device_put(x, sharding), jax.device_put(y, sharding))

    def _shard_superbatch(self, sb):
        """Device layout for a stacked ``[K, batch, ...]`` superbatch:
        the step axis replicates (the fused loop's scan consumes it), the
        batch rows shard over the mesh axis — per step exactly the
        ``_shard_batch`` contract. Issued via DeviceFeed one superbatch
        ahead, so the K× transfer overlaps the previous loop's compute."""
        x = np.asarray(sb.input)
        y = np.asarray(sb.target)
        ndev = self.mesh.shape[self.axis]
        sharding = NamedSharding(self.mesh, P(None, self.axis))
        k = self.accumulate_steps
        if jax.process_count() > 1:
            if (x.shape[1] * jax.process_count()) % ndev:
                raise ValueError(
                    f"local batch {x.shape[1]} x {jax.process_count()} hosts "
                    f"must divide the mesh's '{self.axis}' axis ({ndev})")
            rows = x.shape[1] * jax.process_count() // ndev
            if k > 1 and rows % k:
                raise ValueError(
                    f"accumulate_steps={k} must divide the per-device "
                    f"batch rows ({rows}); keep SampleToMiniBatch's default "
                    "pad_last=True, or set drop_last=True")
            return (jax.make_array_from_process_local_data(sharding, x),
                    jax.make_array_from_process_local_data(sharding, y))
        if x.shape[1] % ndev:
            raise ValueError(
                f"batch size {x.shape[1]} must be divisible by the mesh's "
                f"'{self.axis}' axis size {ndev} (reference requirement: "
                "batchSize % nodeNumber == 0, Optimizer.scala)")
        if k > 1 and (x.shape[1] // ndev) % k:
            raise ValueError(
                f"accumulate_steps={k} must divide the per-device batch "
                f"rows ({x.shape[1] // ndev}); keep SampleToMiniBatch's "
                "default pad_last=True, or set drop_last=True")
        return (jax.device_put(x, sharding), jax.device_put(y, sharding))

    def _superbatch_epoch(self, ds, loop_fn, ahead, driver_state,
                          flat_weights, model_state, opt_shard, rng,
                          step_wire_bytes):
        """One epoch in ``steps_per_loop`` mode (see LocalOptimizer's
        twin): superbatches stack on the Prefetch producer thread, shard
        to the mesh double-buffered (DeviceFeed + ``_shard_superbatch``),
        and each feeds one fused K-step ``lax.scan`` dispatch of the
        shard_map'd distributed step (``step_fn.train_loop``). Trigger
        boundaries truncate the scan via ``_plan_chunk``; the ZeRO-1
        sharded opt state is donated across the whole loop. Returns the
        advanced (flat_weights, model_state, opt_shard, rng, records)."""
        from bigdl_tpu.dataset.transformer import (DeviceFeed, Prefetch,
                                                   ToSuperBatch)
        feed = DeviceFeed(self._shard_superbatch)(Prefetch(2)(
            ToSuperBatch(self.steps_per_loop)(ds.data(train=True))))
        records = 0
        t_data = time.time()
        for sb, (xs, ys) in feed:
            rng, subs = _split_chain(rng, sb.k)
            start = 0
            while start < sb.k:
                j = self._plan_chunk(driver_state, sb.k - start)
                if start == 0 and j == sb.k:
                    cr, cx, cy = subs, xs, ys
                else:
                    # step axis is replicated, so this slice is local
                    sl = slice(start, start + j)
                    cr, cx, cy = subs[sl], xs[sl], ys[sl]
                t0 = time.time()
                self.metrics["data_time"] += t0 - t_data
                obs.record_span("train/feed", t_data, t0,
                                neval=driver_state["neval"])
                fault_point("train.step", neval=driver_state["neval"])
                with obs.span("train/dispatch",
                              neval=driver_state["neval"], k=j):
                    flat_weights, model_state, opt_shard, losses = loop_fn(
                        flat_weights, model_state, opt_shard, cr, cx, cy)
                n = sum(sb.sizes[start:start + j])
                ahead.push(losses, n, t0, k=j)
                records += n
                self.metrics["steps"] += j
                self.metrics["dispatches"] += 1
                self.metrics["step_time"] += time.time() - t0
                self.metrics["allreduce_bytes"] += step_wire_bytes * j
                record_allreduce(step_wire_bytes * j)
                self.metrics["records"] += n
                driver_state["neval"] += j
                opt_shard = self._hooks(driver_state, flat_weights,
                                        model_state, opt_shard, ahead=ahead)
                if self.end_when(driver_state):
                    return (flat_weights, model_state, opt_shard, rng,
                            records)
                start += j
                t_data = time.time()
        return flat_weights, model_state, opt_shard, rng, records

    def optimize(self):
        ds = self.dataset
        first = next(iter(ds.data(train=False)))
        self._ensure_ready(first)
        self._install_preempt_guard()
        model = self.model
        ndev = self.mesh.shape[self.axis]
        # fresh accounting per optimize() call, same contract as
        # LocalOptimizer — a warmup call must not pollute a measured one
        self.metrics = {"allreduce_bytes": 0, "steps": 0,
                        "data_time": 0.0, "step_time": 0.0,
                        "records": 0, "dispatches": 0}

        step_factory = make_distributed_train_step(
            model, self.criterion, self.optim_method, self.mesh,
            axis=self.axis, clipping=self.clipping,
            wire_dtype=self.wire_dtype, compute_dtype=self.compute_dtype,
            accumulate_steps=self.accumulate_steps)
        step_fn, flat_weights, opt_shard = step_factory(model.params)
        model_state = jax.device_put(
            model.state, NamedSharding(self.mesh, P()))
        rng = jax.random.key(self.rng_seed)
        from bigdl_tpu.parallel.allreduce import ring_allreduce_bytes
        step_wire_bytes = ring_allreduce_bytes(flat_weights.shape[0], ndev,
                                               self.wire_dtype)

        driver_state = {"epoch": 1, "neval": 1, "loss": None, "score": None,
                        "epoch_finished": False}
        # Pipelined loss readout — see optim.optimizer._DispatchAhead for
        # the rationale and the BIGDL_TPU_DISPATCH_AHEAD contract.
        from bigdl_tpu.optim.optimizer import _DispatchAhead

        def log_iter(ent, loss_f, rate):
            logger.info(
                "[%d dev] Epoch %d iter %d loss %.4f "
                "throughput %.1f records/s",
                ndev, ent["epoch"], ent["neval"], loss_f, rate)

        ahead = _DispatchAhead(driver_state, self.train_summary, log_iter,
                               loop="distri")

        retries, last_failure = 0, None
        while not self.end_when(driver_state):
            try:
                ds.shuffle()
                driver_state["epoch_finished"] = False
                records, t_epoch = 0, time.time()
                t_data = time.time()
                ahead.reset_epoch()
                if self.steps_per_loop > 1:
                    (flat_weights, model_state, opt_shard, rng,
                     records) = self._superbatch_epoch(
                        ds, step_fn.train_loop, ahead, driver_state,
                        flat_weights, model_state, opt_shard, rng,
                        step_wire_bytes)
                else:
                    for batch in ds.data(train=True):
                        rng, sub = jax.random.split(rng)
                        x, y = self._shard_batch(batch)
                        t0 = time.time()
                        self.metrics["data_time"] += t0 - t_data
                        obs.record_span("train/feed", t_data, t0,
                                        neval=driver_state["neval"])
                        fault_point("train.step",
                                    neval=driver_state["neval"])
                        with obs.span("train/dispatch",
                                      neval=driver_state["neval"]):
                            flat_weights, model_state, opt_shard, loss = \
                                step_fn(flat_weights, model_state,
                                        opt_shard, sub, x, y)
                        n = batch.size()
                        ahead.push(loss, n, t0)
                        records += n
                        self.metrics["steps"] += 1
                        self.metrics["dispatches"] += 1
                        self.metrics["step_time"] += time.time() - t0
                        self.metrics["allreduce_bytes"] += step_wire_bytes
                        record_allreduce(step_wire_bytes)
                        self.metrics["records"] += n
                        driver_state["neval"] += 1
                        opt_shard = self._hooks(driver_state, flat_weights,
                                                model_state, opt_shard,
                                                ahead=ahead)
                        if self.end_when(driver_state):
                            break
                        t_data = time.time()
                t_tail = time.time()
                ahead.drain_all()   # epoch boundary: catch up before hooks
                self.metrics["step_time"] += time.time() - t_tail
                driver_state["epoch_finished"] = True
                opt_shard = self._hooks(driver_state, flat_weights,
                                        model_state, opt_shard)
                logger.info("Epoch %d done (%d records, %.1fs)",
                            driver_state["epoch"], records,
                            time.time() - t_epoch)
                driver_state["epoch"] += 1
                # keep epoch-based LR schedules live in the sharded state —
                # on the leaf's own mesh sharding: a plain array here
                # changes the step's input type and recompiles the whole
                # train step at the first epoch boundary
                opt_shard = {**opt_shard, "epoch": jax.device_put(
                    np.int32(driver_state["epoch"]),
                    opt_shard["epoch"].sharding)}
            except TrainingPreempted:
                # deliberate exit with a final checkpoint already written
                # (_check_preempt) — retrying would defeat the preemption
                raise
            except Exception:
                # collective failure: reload latest checkpoint and rebuild
                # (reference DistriOptimizer.scala:907-976). In-flight
                # dispatched steps belong to the failed run — drop them.
                ahead.clear()
                now = time.time()
                if (last_failure is not None
                        and now - last_failure > self.failure_retry_interval):
                    retries = 0
                last_failure = now
                retries += 1
                if retries > self.failure_retry_times or not self.checkpoint_path:
                    raise
                logger.exception("training failed; retry %d from checkpoint",
                                 retries)
                flat_weights, model_state, opt_shard, driver_state = \
                    self._reload_latest(step_factory)
                # the reload rebinds driver_state to a fresh dict; the
                # drain pipeline must stamp/write THAT one from now on
                ahead.driver_state = driver_state

        self._materialize(flat_weights, model_state, opt_shard)
        self._join_checkpoint()
        return model

    # ------------------------------------------------------------------ util
    def metrics_summary(self):
        """Readable per-phase averages (reference: ``Metrics.summary``,
        ``optim/Metrics.scala:103``)."""
        # base fields: wall-clock throughput (feed wait + device pipeline
        # both counted — the number a user actually gets; reference logs
        # records/s per iteration, DistriOptimizer.scala:388-394) and
        # feed_wait_frac (≈0 means feed/compute overlap is working)
        out = super().metrics_summary()
        m = self.metrics
        out["allreduce_bytes_total"] = m["allreduce_bytes"]
        out["allreduce_wire_gbps_est"] = (
            m["allreduce_bytes"] / m["step_time"] / 1e9
            if m["step_time"] > 0 else 0.0)
        return out

    def _materialize(self, flat_weights, model_state, opt_shard):
        from bigdl_tpu.parallel.allreduce import AllReduceParameter
        arp = AllReduceParameter(self.model.params, self.mesh.shape[self.axis],
                                 self.wire_dtype)
        # cross-host sharded leaves gather, local/replicated leaves copy
        # (the analog of the reference's getModel slice collection,
        # DistriOptimizer.scala:765-797)
        from bigdl_tpu.optim.optimizer import _gather_to_host
        flat = _gather_to_host(flat_weights)
        state = _gather_to_host(model_state)
        self.model.params = arp.to_params(flat)
        self.model.state = state
        self.model.grad_params = tree_zeros_like(self.model.params)
        self._opt_state = opt_shard

    def _validate_inmesh(self, flat_weights, model_state):
        """Sharded validation: forward + psum'd metric counters inside one
        jitted program per batch — weights never materialize to host
        (reference ``optim/DistriValidator.scala:35`` validates in place
        across executors). Returns None when a custom ValidationMethod has
        no counter form (caller falls back to the host path)."""
        if self.validation_dataset is None or not self.validation_methods:
            return {}
        from bigdl_tpu.optim.validation import ValidationMethod
        methods = self.validation_methods
        if any(type(m).counters is ValidationMethod.counters
               for m in methods):
            return None
        if self._eval_fn is None:
            from bigdl_tpu.parallel.allreduce import \
                make_distributed_eval_step
            self._eval_fn = make_distributed_eval_step(
                self.model, methods, self.mesh, self.axis,
                self.wire_dtype, self.compute_dtype)(self.model.params)
        agg = {m.name: None for m in methods}
        for batch in self.validation_dataset.data(train=False):
            size = batch.size()
            real = getattr(batch, "real_size", size)
            if real < size and not getattr(self._eval_fn, "supports_valid",
                                           True):
                # a custom two-arg ValidationMethod cannot mask; its
                # padded rows would skew psum'd counters, so the tail is
                # skipped (logged) — the host path covers exact counts
                logger.warning(
                    "in-mesh validation skipping padded tail batch "
                    "(%d real of %d): custom ValidationMethod without "
                    "mask support", real, size)
                continue
            x, y = self._shard_batch(batch)
            # mask the padded tail inside the jitted step: every real
            # sample — and only real samples — reaches the counters
            # (reference optim/DistriValidator.scala:25 counts exactly)
            valid = self._shard_valid(size, real)
            res = self._eval_fn(flat_weights, model_state, x, y, valid)
            for m, (v, c) in zip(methods, res):
                r = m.make_result(float(v), float(c))
                agg[m.name] = r if agg[m.name] is None else agg[m.name] + r
        return {k: v for k, v in agg.items() if v is not None}

    def _hooks(self, driver_state, flat_weights, model_state, opt_shard,
               ahead=None):
        self._opt_state = opt_shard
        # at most ONE host materialize per hook invocation, shared by every
        # trigger that fires this iteration (each is an allgather + host
        # copy + unravel of all weights)
        materialized = [False]

        def materialize_once():
            if not materialized[0]:
                self._materialize(flat_weights, model_state, opt_shard)
                materialized[0] = True

        def preempt_save():
            from bigdl_tpu.utils.engine import get_flag
            if get_flag("BIGDL_TPU_SHARDED_CHECKPOINT", False, bool):
                self._checkpoint_sharded(driver_state["neval"],
                                         flat_weights, model_state,
                                         opt_shard)
            else:
                materialize_once()
                self._checkpoint(driver_state["neval"])
            self._save_driver_state(driver_state)

        self._check_preempt(driver_state, ahead, preempt_save)
        do_val = (self.validation_trigger is not None
                  and self.validation_trigger(driver_state))
        do_ckpt = (self.checkpoint_trigger is not None
                   and self.checkpoint_trigger(driver_state))
        ts = self.train_summary
        trig = getattr(ts, "_summary_trigger", {}).get("Parameters") \
            if ts is not None else None
        do_hist = trig is not None and trig(driver_state)
        if ahead is not None and (do_val or do_ckpt or do_hist):
            # catch the pipelined loss readout up before any hook runs:
            # _save_driver_state persists driver_state, and without the
            # drain its "loss" (and the Loss summary scalars) would lag
            # `depth` dispatches behind the checkpointed neval
            ahead.drain_all()
        if do_val:
            with obs.span("train/validate", neval=driver_state["neval"]):
                results = self._validate_inmesh(flat_weights, model_state)
                if results is None:
                    materialize_once()
                    results = self._validate(self.model.params,
                                             self.model.state)
            if results:
                score = next(iter(results.values()))
                driver_state["score"] = score
                opt_shard = self._record_plateau(score, opt_shard)
                self._opt_state = opt_shard
                if self.validation_summary is not None:
                    for name, v in results.items():
                        self.validation_summary.add_scalar(
                            name, v, driver_state["neval"])
        if do_ckpt:
            from bigdl_tpu.utils.engine import get_flag
            with obs.span("train/checkpoint", neval=driver_state["neval"]):
                if get_flag("BIGDL_TPU_SHARDED_CHECKPOINT", False, bool):
                    # gather-free: each host writes only its addressable
                    # shards — no full-model all-gather per checkpoint
                    self._checkpoint_sharded(driver_state["neval"],
                                             flat_weights, model_state,
                                             opt_shard)
                else:
                    materialize_once()
                    self._checkpoint(driver_state["neval"])
                self._save_driver_state(driver_state)
        if do_hist:
            # reference: Parameters histograms on their own trigger
            # (TrainSummary.scala:55-88, DistriOptimizer.scala:538-569)
            materialize_once()
            from jax.flatten_util import ravel_pytree
            flat, _ = ravel_pytree(self.model.params)
            ts.add_histogram("Parameters", np.asarray(flat),
                             driver_state["neval"])
        return opt_shard

    # ------------------------------------------- sharded checkpointing --
    # BIGDL_TPU_SHARDED_CHECKPOINT=1: the TPU-native alternative to the
    # reference's driver-collected snapshot (DistriOptimizer.scala:765-797
    # gathers every slice to the driver). Each host serializes ONLY its
    # addressable shards of the f32 master weights + ZeRO-1 optimizer
    # slots, so checkpoint cost stays O(model/n_hosts) per host and no
    # cross-host all-gather runs at all; process 0 adds topology +
    # hyperparameters. Restore maps each saved block back onto the fresh
    # shardings by global offset.

    @staticmethod
    def _local_blocks(arr):
        """[(global_start, ndarray)] for this process's addressable shards
        of a 1-D sharded array; [(None, ndarray)] for replicated/scalar
        leaves (every host keeps its own copy — tiny)."""
        from bigdl_tpu.optim.optimizer import _detach
        if not isinstance(arr, jax.Array) or arr.ndim == 0 \
                or arr.is_fully_replicated:
            return [(None, _detach(np.asarray(jax.device_get(arr))))]
        seen = {}
        for sh in arr.addressable_shards:
            start = sh.index[0].start or 0
            if start not in seen:
                seen[start] = _detach(np.asarray(sh.data))
        return sorted(seen.items())

    @staticmethod
    def _from_blocks(blocks, like):
        """Rebuild a device array with ``like``'s sharding from saved
        (global_start, ndarray) blocks."""
        if blocks[0][0] is None:
            return jax.device_put(blocks[0][1], like.sharding)
        data = dict(blocks)

        def cb(index):
            start = index[0].start or 0
            if start not in data:
                raise RuntimeError(
                    "sharded checkpoint does not cover offset "
                    f"{start}: it was written with a different process/"
                    "device layout — restore with the same topology or "
                    "use the gathered checkpoint format")
            return data[start]

        return jax.make_array_from_callback(like.shape, like.sharding, cb)

    def _checkpoint_sharded(self, neval, flat_weights, model_state,
                            opt_shard):
        import copy
        from jax.tree_util import tree_flatten_with_path, keystr

        from bigdl_tpu.optim.optimizer import _host_snapshot
        if not self.checkpoint_path:
            return
        self._join_checkpoint()
        pid = jax.process_index()
        # snapshot to host synchronously (donated buffers — same rule as
        # Optimizer._checkpoint); pickling and file IO go async
        leaves, _ = tree_flatten_with_path(opt_shard)
        payload = {
            "neval": neval, "pid": pid, "nprocs": jax.process_count(),
            "flat": self._local_blocks(flat_weights),
            "opt": {keystr(path): self._local_blocks(v)
                    for path, v in leaves},
            "state": _host_snapshot(model_state),
        }
        model = None
        if pid == 0:
            # topology + optim hyperparams; weights live in the shard
            # files, so the module's host params are NOT refreshed here.
            # The marker makes that explicit on disk: load_module refuses
            # the file when the shard set it points at is gone, instead of
            # silently serving init-stale weights.
            model = copy.copy(self.model)
            model.params = _host_snapshot(self.model.params)
            model.state = _host_snapshot(model_state)
            model._sharded_weights_marker = {
                "neval": int(neval), "nprocs": jax.process_count()}

        method = self.optim_method

        def write():
            import pickle
            from bigdl_tpu.utils.fileio import (atomic_write, file_makedirs,
                                                path_join)
            file_makedirs(self.checkpoint_path)
            # atomic: a truncated shard file must never count toward a
            # "complete" set on resume
            atomic_write(path_join(self.checkpoint_path,
                                   f"shard.{neval}.p{pid}"),
                         pickle.dumps(payload))
            if pid == 0:
                # optimizer SLOTS live in the shard files; the optimMethod
                # file carries hyperparameters only (state=None) —
                # device_get on the sharded slots would need exactly the
                # cross-host gather this format exists to avoid
                self._write_model_and_method(neval, model, None, method)

        self._spawn_ckpt_writer(f"ckpt-shard-{neval}", write)

    @staticmethod
    def _shard_groups(files):
        """{neval: {pids}} parsed from shard.* checkpoint filenames."""
        by_neval = {}
        for f in files:
            if f.startswith("shard.") and not f.endswith(".tmp"):
                try:
                    _, n, p = f.split(".")
                    by_neval.setdefault(int(n), set()).add(int(p[1:]))
                except ValueError:
                    continue
        return by_neval

    def _reload_sharded(self, neval, step_factory):
        """Restore flat weights + ZeRO-1 slots from the sharded set at
        ``neval`` (selection happens in ``_reload_latest``)."""
        import pickle
        from jax.tree_util import tree_flatten_with_path, keystr
        from bigdl_tpu.utils.fileio import file_open, path_join
        from bigdl_tpu.utils.serializer import load_module
        loaded = load_module(path_join(self.checkpoint_path,
                                       f"model.{neval}"))
        method, _ = type(self.optim_method).load(
            path_join(self.checkpoint_path, f"optimMethod.{neval}"))
        self.optim_method = method
        step_fn, flat_weights, opt_shard = step_factory(loaded.params)
        with file_open(path_join(self.checkpoint_path,
                                 f"shard.{neval}.p{jax.process_index()}"),
                       "rb") as f:
            mine = pickle.load(f)
        flat_weights = self._from_blocks(mine["flat"], flat_weights)
        path_leaves, treedef = tree_flatten_with_path(opt_shard)
        restored = [self._from_blocks(mine["opt"][keystr(path)], fresh)
                    for path, fresh in path_leaves]
        opt_shard = jax.tree_util.tree_unflatten(treedef, restored)
        self.model.state = mine["state"]
        model_state = jax.device_put(mine["state"],
                                     NamedSharding(self.mesh, P()))
        return flat_weights, model_state, opt_shard

    def _save_driver_state(self, driver_state):
        # written atomically WITH each checkpoint, both as .latest and keyed
        # by neval so resume always pairs driver state with the model file it
        # actually reloads (never a stale/newer counter)
        import pickle
        from bigdl_tpu.utils.fileio import (atomic_write, file_makedirs,
                                            path_join)
        if jax.process_count() > 1 and jax.process_index() != 0:
            return   # one writer, same rule as _checkpoint
        # the model/optim write runs on the async checkpoint thread and
        # creates the directory there; this synchronous write must not
        # lose the race with it
        file_makedirs(self.checkpoint_path)
        payload = pickle.dumps(driver_state)
        for name in ("driverState.latest",
                     f"driverState.{driver_state['neval']}"):
            # a crash mid-write must never truncate .latest (atomic swap
            # locally; object-store PUTs are atomic per object — reference
            # goes through the hadoop FS API the same way, File.scala:26)
            atomic_write(path_join(self.checkpoint_path, name), payload)

    def _reload_latest(self, step_factory):
        import pickle
        from bigdl_tpu.utils.fileio import file_listdir, file_open, path_join
        from bigdl_tpu.utils.serializer import load_module
        # an in-flight async write must land before we pick "latest"
        try:
            self._join_checkpoint()
        except RuntimeError:
            logger.exception("pending checkpoint write failed; retrying "
                             "from the previous complete snapshot")
        if jax.process_count() > 1:
            # only host 0 owns the writer thread; the others must not list
            # the shared dir until its join above has landed, or hosts can
            # disagree on "latest" (and then deadlock on mismatched
            # collectives). This barrier runs over the coordination
            # service, which survives a failed training collective.
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices("bigdl_ckpt_reload")
        all_files = file_listdir(self.checkpoint_path)
        # candidate selection across BOTH checkpoint formats: a model.N
        # written by sharded mode holds STALE params (weights live in the
        # shard files), so it is a gathered candidate only when no shard
        # group claims its N. Newest restorable candidate wins regardless
        # of format — switching the flag mid-run must never rewind past a
        # newer snapshot of the other kind.
        groups = self._shard_groups(all_files)
        nprocs = jax.process_count()
        # equality, not superset: a set written by MORE processes does not
        # cover this layout's shard offsets either — only an exact layout
        # match is restorable
        complete = [n for n, pids in groups.items()
                    if pids == set(range(nprocs))
                    and f"model.{n}" in all_files
                    and f"optimMethod.{n}" in all_files]
        # same defensive parse as _shard_groups: a crash between the
        # model.N and optimMethod.N renames (or a stray model.N.tmp left
        # by a killed atomic swap) must demote N to "not a candidate",
        # falling back to the previous complete snapshot instead of
        # raising mid-restore
        gathered = []
        for f in all_files:
            if not f.startswith("model."):
                continue
            try:
                n = int(f.split(".")[1])
            except (IndexError, ValueError):
                continue
            if f != f"model.{n}":       # skips model.N.tmp and friends
                continue
            if n in groups or f"optimMethod.{n}" not in all_files:
                continue
            gathered.append(n)
        # newest first across both formats (sharded preferred on a tie);
        # a candidate that fails to RESTORE (truncated/garbled file —
        # storage corruption the atomic rename cannot defend against)
        # demotes to the next-older one instead of killing the retry
        candidates = sorted(
            [(n, "sharded") for n in complete]
            + [(n, "gathered") for n in gathered],
            key=lambda t: (t[0], t[1] == "sharded"), reverse=True)
        if not candidates:
            if groups:
                # shard files exist but no set is restorable with this
                # layout; the gathered model.N twins of those sets hold
                # STALE params — silently resuming from them would restart
                # training from init while driver_state claims progress
                raise RuntimeError(
                    f"sharded checkpoint sets {sorted(groups)} exist but "
                    f"none is complete for {nprocs} process(es) — restore "
                    "with the layout that wrote them")
            raise RuntimeError("no checkpoint to retry from")
        last_err = None
        for neval, kind in candidates:
            try:
                if kind == "sharded":
                    (flat_weights, model_state,
                     opt_shard) = self._reload_sharded(neval, step_factory)
                else:
                    loaded = load_module(
                        path_join(self.checkpoint_path, f"model.{neval}"))
                    self.model.params = loaded.params
                    self.model.state = loaded.state
                    method, saved_opt = type(self.optim_method).load(
                        path_join(self.checkpoint_path,
                                  f"optimMethod.{neval}"))
                    self.optim_method = method
                    step_fn, flat_weights, opt_shard = step_factory(
                        self.model.params)
                    if saved_opt is not None:
                        # restore optimizer slots (Adam moments, step
                        # counter, ...) onto the fresh shardings — losing
                        # them would spike the LR on resume
                        opt_shard = jax.tree_util.tree_map(
                            lambda fresh, saved: jax.device_put(
                                saved, fresh.sharding),
                            opt_shard, saved_opt)
                    model_state = jax.device_put(
                        self.model.state, NamedSharding(self.mesh, P()))
                # donation safety: the restored leaves can alias host
                # memory (``jnp.asarray``/``device_put`` over the
                # unpickled checkpoint is zero-copy on the CPU backend),
                # and the train step DONATES them — the runtime then
                # frees buffers it does not own, corrupting the heap
                # (observed: malloc smallbin aborts after a retry). A
                # jitted copy always allocates fresh runtime-owned
                # output buffers, severing every alias chain in one
                # dispatch.
                (flat_weights, model_state, opt_shard) = jax.jit(
                    lambda t: jax.tree_util.tree_map(jnp.copy, t))(
                        (flat_weights, model_state, opt_shard))
                break
            except Exception as e:
                last_err = e
                logger.warning(
                    "checkpoint %d (%s) failed to restore (%r); falling "
                    "back to an older snapshot", neval, kind, e)
        else:
            raise RuntimeError(
                "no checkpoint to retry from (all "
                f"{len(candidates)} candidate(s) failed to restore)"
            ) from last_err
        # prefer the driver state written with THIS model checkpoint
        from bigdl_tpu.utils.fileio import file_exists
        ds_path = path_join(self.checkpoint_path, f"driverState.{neval}")
        if not file_exists(ds_path):
            ds_path = path_join(self.checkpoint_path, "driverState.latest")
        if file_exists(ds_path):
            with file_open(ds_path, "rb") as f:
                driver_state = pickle.load(f)
        else:
            driver_state = {"epoch": 1, "neval": neval, "loss": None,
                            "score": None, "epoch_finished": False}
        return flat_weights, model_state, opt_shard, driver_state
