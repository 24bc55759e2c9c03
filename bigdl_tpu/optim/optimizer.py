"""Optimizer facade + single-device training loop.

Reference: ``optim/Optimizer.scala:42`` (facade/factory: model, dataset,
criterion, endWhen, checkpoint, validation, summaries, clipping) and
``optim/LocalOptimizer.scala:42``. The reference's inner loop clones the
model per core and aggregates thread-local gradients; TPU-natively the whole
iteration — forward, backward, clipping, optimizer update — is ONE jitted
``train_step`` whose intra-chip parallelism belongs to XLA. The host loop
only pumps batches and evaluates triggers, mirroring the driver side of
``DistriOptimizer.optimize`` (``DistriOptimizer.scala:90-493``).
"""

from __future__ import annotations

import functools
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bigdl_tpu import obs
from bigdl_tpu.nn.module import tree_add, tree_zeros_like
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.optim.methods import OptimMethod
from bigdl_tpu.resilience import faults
from bigdl_tpu.resilience.faults import fault_point

logger = logging.getLogger("bigdl_tpu.optim")


def clip_by_global_norm(grads, max_norm):
    leaves = jax.tree_util.tree_leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-12))
    return jax.tree_util.tree_map(lambda g: g * scale, grads)


def clip_by_value(grads, min_value, max_value):
    return jax.tree_util.tree_map(
        lambda g: jnp.clip(g, min_value, max_value), grads)


# Owning-copy guards live in utils.hostcopy (shared with the serving KV
# snapshot writer); the old private names remain importable for callers.
from bigdl_tpu.utils.hostcopy import detach as _detach          # noqa: E402
from bigdl_tpu.utils.hostcopy import host_snapshot as _host_snapshot  # noqa: E402


def _gather_to_host(tree):
    """Host copies of a pytree that may hold cross-host sharded arrays
    (ZeRO-1 optimizer slots live sharded over the mesh's data axis).
    ``device_get`` alone raises on non-fully-addressable arrays, so those
    leaves are all-gathered across processes first; replicated/local
    leaves take the direct copy path."""
    def leaf(v):
        if isinstance(v, jax.Array) and not v.is_fully_addressable:
            from jax.experimental import multihost_utils
            return multihost_utils.process_allgather(v, tiled=True)
        return _detach(jax.device_get(v))
    return jax.tree_util.tree_map(leaf, tree)


class _DispatchAhead:
    """Pipelined per-step loss readout shared by LocalOptimizer and
    DistriOptimizer.

    Reading a step's loss on the host blocks until that step finishes on
    device, so a sync inside the loop caps the pipeline at one step and the
    device idles for the host's per-call dispatch overhead every iteration.
    Instead the host dispatches step N, then reads step N-`depth`'s loss —
    the device always has the next step enqueued. The reference driver
    reads loss synchronously (``DistriOptimizer.scala:388-394``) but had no
    async dispatch to lose; here log lines and summaries report the DRAINED step,
    each stamped with its own iteration number, so values lag `depth`
    iterations and loss-based end triggers may overshoot by up to `depth`
    steps. ``BIGDL_TPU_DISPATCH_AHEAD=0`` restores the synchronous loop.

    With ``steps_per_loop`` > 1 one push covers a whole fused K-step
    dispatch (``push(losses, n, t0, k=K)`` with a stacked ``[K]`` loss
    vector): the queue depth then counts SUPERBATCHES in flight, and a
    drain replays every per-step loss into the summary under its own
    iteration number so trigger/metric consumers still see each step.
    """

    def __init__(self, driver_state, summary, log_fn, loop="local"):
        from collections import deque
        from bigdl_tpu import obs
        from bigdl_tpu.utils.engine import get_flag
        self.depth = max(0, get_flag("BIGDL_TPU_DISPATCH_AHEAD", 1, int))
        self.pending = deque()
        self.driver_state = driver_state
        self.summary = summary
        self.log_fn = log_fn       # callable(ent, loss_f, rate)
        self.last_drain = None
        self.last_rate = None
        # obs: both optimizers route every step through here, so this is
        # the one place that owns the training-loop instruments (series
        # labeled by loop, "local"/"distri")
        reg = obs.default_registry()
        lbl = ("loop",)
        self._obs_steps = reg.counter(
            "bigdl_train_steps_total", "optimizer steps completed",
            lbl).labels(loop)
        self._obs_records = reg.counter(
            "bigdl_train_records_total", "training records consumed",
            lbl).labels(loop)
        self._obs_dispatches = reg.counter(
            "bigdl_train_dispatches_total",
            "jitted train-step/loop launches", lbl).labels(loop)
        self._obs_rate = reg.gauge(
            "bigdl_train_records_per_sec",
            "drained-step training throughput", lbl).labels(loop)
        self._obs_queue = reg.gauge(
            "bigdl_train_dispatch_queue_depth",
            "dispatched-ahead steps awaiting loss readback", lbl).labels(loop)
        # the drain's device_get is the loop's one blocking sync — in the
        # distributed loop it is where a slow/hung allreduce surfaces, so
        # a configurable budget turns "mysteriously slow" into a counter
        self.sync_timeout_s = get_flag("BIGDL_TPU_SYNC_TIMEOUT_S",
                                       0.0, float)
        self._obs_sync_timeouts = reg.counter(
            "bigdl_sync_timeouts_total",
            "blocking loss-readback syncs over BIGDL_TPU_SYNC_TIMEOUT_S",
            lbl).labels(loop)
        self._obs_span = obs.span
        self.anomaly = obs.StepTimeAnomalyDetector(loop=loop)

    def push(self, loss, n, t0, k=1):
        """Register the just-dispatched step (or fused ``k``-step loop,
        whose ``loss`` is the stacked ``[k]`` vector), then catch up to
        `depth`."""
        self.pending.append({"loss": loss, "n": n, "t0": t0, "k": k,
                             "neval": self.driver_state["neval"],
                             "epoch": self.driver_state["epoch"]})
        self._obs_dispatches.inc()
        while len(self.pending) > self.depth:
            self._drain_one()
        self._obs_queue.set(len(self.pending))

    def drain_all(self):
        """Epoch boundary / end of training: read every outstanding loss
        so driver_state and summaries are current before hooks run."""
        while self.pending:
            self._drain_one()

    def reset_epoch(self):
        # between epochs the host runs hooks/validation; the next drain's
        # rate should not span that gap
        self.last_drain = None

    def clear(self):
        """Failure path: in-flight steps belong to the failed run."""
        self.pending.clear()
        self.last_drain = None
        self.last_rate = None

    def _drain_one(self):
        import numpy as np
        ent = self.pending.popleft()
        k = ent.get("k", 1)
        # sync point: ent's step (or whole fused loop) is done. ONE
        # device_get pulls the entire fused K-vector to the host; the
        # summary loop below then reads host floats instead of issuing a
        # per-step readback against the device array
        with self._obs_span("train/drain", neval=ent["neval"], k=k):
            t_sync = time.perf_counter()
            # inside the timed window: an injected straggler delay is
            # indistinguishable from a genuinely slow collective, so it
            # exercises the sync-timeout accounting below
            fault_point("train.drain", neval=ent["neval"])
            losses = np.asarray(jax.device_get(ent["loss"]),
                                np.float32).reshape(-1)
            sync_s = time.perf_counter() - t_sync
        if self.sync_timeout_s > 0 and sync_s > self.sync_timeout_s:
            self._obs_sync_timeouts.inc()
            logger.warning(
                "loss readback for iteration %d blocked %.3fs "
                "(budget %.3fs): device sync — in the distributed loop, "
                "the allreduce — is running long", ent["neval"], sync_s,
                self.sync_timeout_s)
        loss_vals = [float(v) for v in losses]
        loss_f = loss_vals[-1]
        now = time.time()
        prev = self.last_drain if self.last_drain is not None else ent["t0"]
        dt = now - prev
        self.last_drain = now
        if dt < 1e-4 and self.last_rate is not None:
            # burst drain (e.g. epoch-tail catch-up with the device already
            # finished): the host observed several completions at once, so
            # the inter-drain interval says nothing about device rate —
            # carry the last steady-state value instead of logging a spike
            rate = self.last_rate
        else:
            rate = ent["n"] / max(dt, 1e-9)
            # steady-state drains pace the device: dt/k approximates one
            # step's wall time, which feeds the rolling-median detector
            self.anomaly.observe(dt / k)
        self.last_rate = rate
        self._obs_steps.inc(k)
        self._obs_records.inc(ent["n"])
        self._obs_rate.set(rate)
        self.driver_state["loss"] = loss_f
        if self.summary is not None:
            # replay every fused step under its own iteration number —
            # summaries and loss consumers can't tell K>1 from K=1
            for i in range(k):
                self.summary.add_scalar("Loss", loss_vals[i],
                                        ent["neval"] + i)
                self.summary.add_scalar("Throughput", rate,
                                        ent["neval"] + i)
        if k > 1:
            ent = {**ent, "neval": ent["neval"] + k - 1}
        self.log_fn(ent, loss_f, rate)


def scan_microbatches(k, rng, x, y, micro_fn, grad_zero,
                      combine=None):
    """Shared gradient-accumulation harness: reshape the batch into K
    micro-batches and ``lax.scan`` ``micro_fn`` over them, accumulating
    gradients (via ``combine``, default pytree add) and loss in f32;
    returns (grads/K, loss/K, final_state). ``micro_fn(state, rng, x, y)
    -> (loss, new_state, grads)`` — the single- and multi-device steps
    differ only in what "grads" is (a pytree vs the padded flat vector),
    everything else stays in lockstep here."""
    combine = combine or tree_add
    xs = jax.tree_util.tree_map(
        lambda v: v.reshape((k, v.shape[0] // k) + v.shape[1:]), x)
    ys = jax.tree_util.tree_map(
        lambda v: v.reshape((k, v.shape[0] // k) + v.shape[1:]), y)

    def micro(carry, sl):
        g_acc, loss_acc, state, i = carry
        mloss, new_state, grads = micro_fn(
            state, jax.random.fold_in(rng, i), sl[0], sl[1])
        return (combine(g_acc, grads), loss_acc + mloss, new_state,
                i + 1), None

    def run(model_state):
        init = (grad_zero, jnp.zeros((), jnp.float32), model_state,
                jnp.zeros((), jnp.int32))
        (grads, loss, state, _), _ = lax.scan(micro, init, (xs, ys))
        grads = jax.tree_util.tree_map(lambda g: g / k, grads)
        return grads, loss / k, state

    return run


def _build_train_step(module, criterion, optim_method, clipping=None,
                      compute_dtype=None, remat=False, accumulate_steps=1):
    """The raw (un-jitted) single-device train step shared by
    :func:`make_train_step` (one jit per step) and :func:`make_train_loop`
    (K steps scanned inside one jit)."""
    scale_tree_needed = module.params is not None and any(
        s != 1.0 for s in jax.tree_util.tree_leaves(
            module.grad_scale_tree(module.params)))

    def _cast(tree, dtype):
        return jax.tree_util.tree_map(
            lambda v: v.astype(dtype)
            if jnp.issubdtype(v.dtype, jnp.floating) else v, tree)

    def _loss_and_grads(params, model_state, rng, x, y):
        def loss_fn(p):
            inp = x
            if compute_dtype is not None:
                # bf16 compute on the MXU; master params stay f32 and the
                # cast is differentiated, so grads come back f32
                inp = _cast(inp, compute_dtype)
                p = _cast(p, compute_dtype)
            fwd = (jax.checkpoint(
                       lambda pp, ii: module.apply(pp, model_state, ii,
                                                   training=True, rng=rng))
                   if remat else
                   lambda pp, ii: module.apply(pp, model_state, ii,
                                               training=True, rng=rng))
            out, new_state = fwd(p, inp)
            if compute_dtype is not None:
                out = jax.tree_util.tree_map(
                    lambda v: v.astype(jnp.float32), out)
            loss = criterion.apply(out, y) + module.regularization_loss(p)
            return loss, new_state

        return jax.value_and_grad(loss_fn, has_aux=True)(params)

    def train_step(params, model_state, opt_state, rng, x, y):
        if accumulate_steps > 1:
            def micro_fn(state, mrng, mx, my):
                (mloss, new_state), grads = _loss_and_grads(
                    params, state, mrng, mx, my)
                return mloss, new_state, grads

            grads, loss, new_model_state = scan_microbatches(
                accumulate_steps, rng, x, y, micro_fn,
                tree_zeros_like(params))(model_state)
        else:
            (loss, new_model_state), grads = _loss_and_grads(
                params, model_state, rng, x, y)
        if scale_tree_needed:
            grads = jax.tree_util.tree_map(
                lambda g, s: g * s, grads, module.grad_scale_tree(params))
        if clipping is not None:
            grads = clipping(grads)
        new_params, new_opt_state = optim_method.update(grads, opt_state,
                                                        params)
        return new_params, new_model_state, new_opt_state, loss

    return train_step


def make_train_step(module, criterion, optim_method, clipping=None,
                    compute_dtype=None, remat=False, accumulate_steps=1):
    """Build the fused single-device train step:
    (params, model_state, opt_state, rng, x, y) ->
    (params, model_state, opt_state, loss).

    ``remat=True`` wraps the whole forward in ``jax.checkpoint`` so the
    backward pass recomputes activations instead of storing them — trades
    FLOPs for activation memory (models with internal structure get finer
    grain from their own flag, e.g. ``BERT(remat=True)`` per layer).

    ``accumulate_steps=K`` scans K micro-batches inside the same jitted
    step (K must divide the batch rows): K× the effective batch at 1×
    activation memory, one optimizer update per step — the single-device
    twin of ``make_distributed_train_step(accumulate_steps=K)``.

    For K full optimizer steps per dispatch see :func:`make_train_loop`
    (the ``steps_per_loop`` execution mode).
    """
    return jax.jit(
        _build_train_step(module, criterion, optim_method, clipping,
                          compute_dtype, remat, accumulate_steps),
        donate_argnums=(0, 1, 2))


def make_train_loop(module, criterion, optim_method, clipping=None,
                    compute_dtype=None, remat=False, accumulate_steps=1):
    """Build the fused K-step train loop (the ``steps_per_loop`` mode):

    ``(params, model_state, opt_state, rngs, xs, ys) ->
    (params, model_state, opt_state, losses)``

    where ``rngs``/``xs``/``ys`` carry a leading step axis ``[K, ...]``
    (a stacked superbatch) and ``losses`` is the ``[K]`` per-step loss
    vector. The whole loop — K× (forward, backward, grad scaling,
    clipping, optimizer update), including the inner ``accumulate_steps``
    micro-batch scan — is ONE ``lax.scan`` inside ONE jitted dispatch, so
    per-step host overhead (dispatch, transfer, readback) drops to
    O(1/K). Params/model_state/opt_state are donated across the whole
    loop. The scan length comes from the leading axis, so each distinct K
    (e.g. a truncated epoch tail) compiles once and is then cached.
    """
    step = _build_train_step(module, criterion, optim_method, clipping,
                             compute_dtype, remat, accumulate_steps)

    def train_loop(params, model_state, opt_state, rngs, xs, ys):
        def body(carry, sl):
            p, ms, os_ = carry
            rng, x, y = sl
            p, ms, os_, loss = step(p, ms, os_, rng, x, y)
            return (p, ms, os_), loss

        (p, ms, os_), losses = lax.scan(
            body, (params, model_state, opt_state), (rngs, xs, ys))
        return p, ms, os_, losses

    return jax.jit(train_loop, donate_argnums=(0, 1, 2))


@functools.partial(jax.jit, static_argnums=1)
def _split_chain(rng, k):
    """The driver's per-step ``rng, sub = jax.random.split(rng)`` chain,
    k links in ONE dispatch. Bit-identical to the sequential host loop,
    so a ``steps_per_loop=K`` superbatch consumes exactly the rng stream
    the K=1 loop would have — trajectory parity holds. Returns
    ``(advanced_rng, subs[k])``."""
    def link(r, _):
        r, s = jax.random.split(r)
        return r, s

    return lax.scan(link, rng, None, length=k)


class Optimizer:
    """Facade + factory (reference ``optim/Optimizer.scala:42,466``)."""

    def __new__(cls, model=None, dataset=None, criterion=None, **kwargs):
        if cls is Optimizer:
            from bigdl_tpu.dataset.dataset import DistributedDataSet
            from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
            if isinstance(dataset, DistributedDataSet) or kwargs.get("mesh"):
                return super().__new__(DistriOptimizer)
            return super().__new__(LocalOptimizer)
        return super().__new__(cls)

    def __init__(self, model=None, dataset=None, criterion=None, **kwargs):
        self.model = model
        self.dataset = dataset
        self.criterion = criterion
        self.optim_method = None
        self.end_when = Trigger.max_epoch(1)
        self.validation_trigger = None
        self.validation_dataset = None
        self.validation_methods = None
        self.checkpoint_trigger = None
        self.checkpoint_path = None
        self.train_summary = None
        self.validation_summary = None
        self.clipping = None
        self.rng_seed = kwargs.get("seed", 1)
        self.metrics = {}
        # K micro-batches scanned inside the jitted step (K must divide
        # the batch rows): K x effective batch, 1 x activation memory
        accumulate_steps = kwargs.get("accumulate_steps", 1)
        if accumulate_steps != int(accumulate_steps) \
                or int(accumulate_steps) < 1:
            raise ValueError(
                f"accumulate_steps must be a positive integer, got "
                f"{accumulate_steps!r}")
        self.accumulate_steps = int(accumulate_steps)
        # K FULL optimizer steps fused into one jitted lax.scan dispatch
        # over a [K, batch, ...] superbatch (see make_train_loop): host
        # overhead per step drops to O(1/K), at the cost of staging K
        # batches on device at once. Defaults to the
        # BIGDL_TPU_STEPS_PER_LOOP flag (1 = the classic per-step loop).
        steps_per_loop = kwargs.get("steps_per_loop")
        if steps_per_loop is None:
            from bigdl_tpu.utils.engine import get_flag
            steps_per_loop = get_flag("BIGDL_TPU_STEPS_PER_LOOP", 1, int)
        if steps_per_loop != int(steps_per_loop) or int(steps_per_loop) < 1:
            raise ValueError(
                f"steps_per_loop must be a positive integer, got "
                f"{steps_per_loop!r}")
        self.steps_per_loop = int(steps_per_loop)

    # ----- builder API (reference setXxx) -----------------------------------
    def set_optim_method(self, method: OptimMethod):
        self.optim_method = method
        return self

    def set_end_when(self, trigger):
        self.end_when = trigger
        return self

    def set_validation(self, trigger, dataset, methods):
        self.validation_trigger = trigger
        self.validation_dataset = dataset
        self.validation_methods = methods
        return self

    def set_checkpoint(self, path, trigger):
        self.checkpoint_path = path
        self.checkpoint_trigger = trigger
        return self

    def set_train_summary(self, summary):
        self.train_summary = summary
        return self

    def set_validation_summary(self, summary):
        self.validation_summary = summary
        return self

    def set_gradient_clipping_by_l2_norm(self, max_norm):
        self.clipping = lambda g: clip_by_global_norm(g, max_norm)
        return self

    def set_constant_gradient_clipping(self, min_value, max_value):
        self.clipping = lambda g: clip_by_value(g, min_value, max_value)
        return self

    def disable_gradient_clipping(self):
        self.clipping = None
        return self

    # ----- shared helpers ---------------------------------------------------
    def _ensure_ready(self, sample_batch):
        if self.optim_method is None:
            from bigdl_tpu.optim.methods import SGD
            self.optim_method = SGD()
        if self.model.params is None:
            x = sample_batch.get_input()
            self.model.build(self.rng_seed, jnp.asarray(x))

    def _plan_chunk(self, driver_state, kmax):
        """Steps the fused loop may run before a trigger needs the host:
        the largest j <= kmax such that no validation/checkpoint/end/
        summary trigger fires strictly inside the chunk (hooks run at the
        chunk boundary, exactly where the K=1 loop would have run them).
        Triggers are probed with simulated future states — neval advanced,
        loss/score frozen at their current values — so iteration-counting
        triggers keep exact K=1 semantics, while loss/score-based ones
        fire at the next boundary (the same up-to-depth overshoot the
        dispatch-ahead queue already documents)."""
        triggers = [self.end_when, self.validation_trigger,
                    self.checkpoint_trigger]
        ts = self.train_summary
        if ts is not None:
            triggers.append(
                getattr(ts, "_summary_trigger", {}).get("Parameters"))
        triggers = [t for t in triggers if t is not None]
        base = dict(driver_state)
        for j in range(1, kmax):
            probe = {**base, "neval": base["neval"] + j}
            if any(t(probe) for t in triggers):
                return j
        return kmax

    def _validate(self, params, model_state):
        results = {}
        if self.validation_dataset is None:
            return results
        from bigdl_tpu.optim.evaluator import Evaluator
        was_training = self.model.train_mode
        saved = (self.model.params, self.model.state)
        self.model.params, self.model.state = params, model_state
        try:
            agg = Evaluator(self.model).evaluate(self.validation_dataset,
                                                 self.validation_methods)
        finally:
            self.model.params, self.model.state = saved
            if was_training:
                self.model.training()
        for name, r in agg.items():
            value, _ = r.result()
            results[name] = value
            logger.info("validation %s = %.4f", name, value)
        return results

    def _record_plateau(self, score, opt_state):
        """Feed the validation score to a Plateau schedule and write the new
        factor into opt_state (see OptimMethod.init_state)."""
        from bigdl_tpu.optim.schedules import Plateau
        sched = getattr(self.optim_method, "schedule", None)
        if isinstance(sched, Plateau) and "plateau_mult" in opt_state:
            mult = sched.record(score)
            return {**opt_state,
                    "plateau_mult": jnp.asarray(mult, jnp.float32)}
        return opt_state

    def _checkpoint(self, neval):
        """Write-behind: serialization + file IO run on a worker thread so
        training resumes immediately (the orbax-style async save; the
        reference blocks the driver, ``Optimizer.scala:412-463``). Writes
        are ordered — the previous write joins before the next starts —
        and any worker exception surfaces at the next trigger or at the
        end of optimize(). ``BIGDL_TPU_ASYNC_CHECKPOINT=0`` restores the
        synchronous reference behavior."""
        if not self.checkpoint_path:
            return
        self._join_checkpoint()
        # snapshot to host BEFORE going async: the live device buffers are
        # donated by the next train step, which would invalidate what the
        # writer thread reads (only the protowire encode + file IO overlap
        # with training; the device->host copy stays synchronous). The
        # writer serializes a DETACHED shallow clone: the main thread keeps
        # mutating self.model.params (validation swaps, DistriOptimizer
        # re-materialization) while the write is in flight, and a shared
        # module object would let those mutations corrupt the snapshot.
        import copy
        model = copy.copy(self.model)
        model.params = _host_snapshot(self.model.params)
        model.state = _host_snapshot(self.model.state)
        opt_state = _gather_to_host(self._opt_state)
        if jax.process_count() > 1 and jax.process_index() != 0:
            # every host participated in the collective gather above, but
            # exactly one writes — concurrent writers would race on the
            # same checkpoint files (reference: the Spark DRIVER owns the
            # write, Optimizer.scala:412-463; checkpoint_path must be
            # shared storage for resume, same contract as the reference)
            return

        method = self.optim_method
        self._spawn_ckpt_writer(
            f"ckpt-{neval}",
            lambda: self._write_model_and_method(neval, model, opt_state,
                                                 method))

    def _write_model_and_method(self, neval, model, opt_state, method=None):
        """Persist topology+weights and optimizer hyperparams/slots —
        shared by the gathered and sharded checkpoint writers so the two
        formats cannot drift in naming/overwrite semantics. Both files
        appear atomically: resume-time snapshot selection counts them by
        filename, so a crash mid-write must not leave truncated files
        under the real names.

        ``method`` is captured by the CALLER, on the main thread: this
        body runs on the writer thread, and reading ``self.optim_method``
        here would race a retry's ``_reload_latest`` swapping it."""
        if method is None:
            method = self.optim_method
        from bigdl_tpu.utils.fileio import (atomic_file_swap, file_makedirs,
                                            path_join)
        from bigdl_tpu.utils.serializer import save_module
        fault_point("ckpt.write", neval=neval)
        file_makedirs(self.checkpoint_path)
        model_path = path_join(self.checkpoint_path, f"model.{neval}")
        method_path = path_join(self.checkpoint_path,
                                f"optimMethod.{neval}")
        atomic_file_swap(
            model_path, lambda p: save_module(model, p, overwrite=True))
        atomic_file_swap(
            method_path,
            lambda p: method.save(p, opt_state, overwrite=True))
        # chaos hook: mangles the JUST-LANDED files when a corrupt rule is
        # armed — simulating storage-level corruption the atomic rename
        # cannot defend against; resume must fall back to an older pair
        faults.corrupt_file("ckpt.write", model_path)
        faults.corrupt_file("ckpt.write", method_path)

    def _spawn_ckpt_writer(self, name, write):
        """Run ``write`` on the checkpoint worker thread (or inline under
        BIGDL_TPU_ASYNC_CHECKPOINT=0); exceptions surface at the next
        join."""
        from bigdl_tpu.utils.engine import get_flag
        if not get_flag("BIGDL_TPU_ASYNC_CHECKPOINT", True, bool):
            write()
            return
        import threading
        exc = []

        def run():
            try:
                write()
            except BaseException as e:  # surfaced at the next join
                exc.append(e)

        t = threading.Thread(target=run, name=name, daemon=True)
        self._ckpt_thread, self._ckpt_exc = t, exc
        t.start()

    def _join_checkpoint(self):
        t = getattr(self, "_ckpt_thread", None)
        if t is not None:
            t.join()
            self._ckpt_thread = None
            exc = getattr(self, "_ckpt_exc", [])
            if exc:
                self._ckpt_exc = []
                raise RuntimeError("async checkpoint write failed") \
                    from exc[0]

    def _install_preempt_guard(self):
        """Arm the SIGTERM handler at optimize() entry (flag-gated by
        ``BIGDL_TPU_PREEMPT_GUARD``, default on; a no-op off the main
        thread — CPython only delivers signals there)."""
        from bigdl_tpu.utils.engine import get_flag
        if get_flag("BIGDL_TPU_PREEMPT_GUARD", True, bool):
            from bigdl_tpu.resilience import preempt
            preempt.install()

    def _check_preempt(self, driver_state, ahead, save):
        """Cooperative preemption point, polled once per optimizer step.
        When the guard observed SIGTERM (or the fault harness injected a
        preemption): drain the dispatch-ahead queue so driver_state's
        loss/neval are current, write a FINAL checkpoint via ``save``,
        join the async writer, and raise
        :class:`~bigdl_tpu.resilience.preempt.TrainingPreempted` — the
        one exception the DistriOptimizer retry loop does not swallow."""
        from bigdl_tpu.resilience import preempt
        if not preempt.requested():
            return
        from bigdl_tpu.resilience.preempt import TrainingPreempted
        reason = preempt.reason()
        if ahead is not None:
            ahead.drain_all()
        neval = driver_state["neval"]
        logger.warning("preempted (%s): writing final checkpoint at "
                       "iteration %d before exit", reason, neval)
        with obs.span("train/preempt_checkpoint", neval=neval):
            if save is not None:
                save()
            self._join_checkpoint()
        raise TrainingPreempted(
            f"training preempted ({reason}); final checkpoint at "
            f"iteration {neval}", neval=neval)

    def optimize(self):
        raise NotImplementedError

    def metrics_summary(self):
        """Readable per-phase averages (reference ``Metrics.summary``,
        ``optim/Metrics.scala:103``); DistriOptimizer extends this with
        the allreduce wire fields."""
        m = self.metrics
        s = max(m.get("steps", 0), 1)
        wall = m.get("data_time", 0.0) + m.get("step_time", 0.0)
        return {"steps": m.get("steps", 0),
                "data_time_avg_s": m.get("data_time", 0.0) / s,
                "step_time_avg_s": m.get("step_time", 0.0) / s,
                "throughput_rec_s": (m.get("records", 0) / wall
                                     if wall > 0 else 0.0),
                "feed_wait_frac": (m.get("data_time", 0.0) / wall
                                   if wall > 0 else 0.0)}


class LocalOptimizer(Optimizer):
    """Single-device loop (reference ``optim/LocalOptimizer.scala:42``).

    With ``steps_per_loop=K`` > 1 the loop runs in superbatch mode: K
    batches are stacked into ``[K, batch, ...]`` arrays on a background
    thread, transferred double-buffered, and consumed by ONE jitted
    K-step ``lax.scan`` (:func:`make_train_loop`) — host overhead per
    optimizer step drops to O(1/K). Triggers are honored exactly: the
    scan is truncated at any boundary where a trigger would fire
    (``Optimizer._plan_chunk``), and per-step losses are replayed into
    summaries/metrics as if K were 1."""

    def optimize(self):
        ds = self.dataset
        first = next(iter(ds.data(train=False)))
        self._ensure_ready(first)
        self._install_preempt_guard()
        model = self.model
        params, model_state = model.params, model.state
        opt_state = self.optim_method.init_state(params)
        if self.steps_per_loop > 1:
            step_fn = None
            loop_fn = make_train_loop(model, self.criterion,
                                      self.optim_method, self.clipping,
                                      accumulate_steps=self.accumulate_steps)
        else:
            step_fn = make_train_step(model, self.criterion,
                                      self.optim_method, self.clipping,
                                      accumulate_steps=self.accumulate_steps)
            loop_fn = None
        rng = jax.random.key(self.rng_seed)
        # same phase accounting as DistriOptimizer: data (feed wait) vs
        # step (dispatch+drain) buckets, read via metrics_summary();
        # "dispatches" counts jitted train invocations (== steps at K=1,
        # ~steps/K in superbatch mode — the number the fused loop shrinks)
        self.metrics = {"steps": 0, "data_time": 0.0, "step_time": 0.0,
                        "records": 0, "dispatches": 0}

        driver_state = {"epoch": 1, "neval": 1, "loss": None, "score": None,
                        "epoch_finished": False}

        def log_iter(ent, loss_f, rate):
            logger.info(
                "Epoch %d iter %d loss %.4f throughput %.1f records/s",
                ent["epoch"], ent["neval"], loss_f, rate)

        ahead = _DispatchAhead(driver_state, self.train_summary, log_iter)
        t_epoch = time.time()
        while not self.end_when(driver_state):
            ds.shuffle()
            driver_state["epoch_finished"] = False
            records = 0
            ahead.reset_epoch()
            if self.steps_per_loop > 1:
                params, model_state, opt_state, rng, records = \
                    self._superbatch_epoch(ds, loop_fn, ahead, driver_state,
                                           params, model_state, opt_state,
                                           rng)
            else:
                t_data = time.time()
                for batch in ds.data(train=True):
                    rng, sub = jax.random.split(rng)
                    x = jnp.asarray(batch.get_input())
                    y = jnp.asarray(batch.get_target())
                    if self.accumulate_steps > 1 \
                            and x.shape[0] % self.accumulate_steps:
                        # per batch: a variable-size tail would otherwise
                        # die inside the jitted micro-batch reshape
                        raise ValueError(
                            f"accumulate_steps={self.accumulate_steps} must "
                            f"divide the batch rows ({x.shape[0]}); keep "
                            "SampleToMiniBatch's default pad_last=True, or "
                            "set drop_last=True")
                    t0 = time.time()
                    self.metrics["data_time"] += t0 - t_data
                    obs.record_span("train/feed", t_data, t0,
                                    neval=driver_state["neval"])
                    fault_point("train.step", neval=driver_state["neval"])
                    with obs.span("train/dispatch",
                                  neval=driver_state["neval"]):
                        params, model_state, opt_state, loss = step_fn(
                            params, model_state, opt_state, sub, x, y)
                    ahead.push(loss, x.shape[0], t0)
                    records += x.shape[0]
                    self.metrics["steps"] += 1
                    self.metrics["dispatches"] += 1
                    self.metrics["step_time"] += time.time() - t0
                    self.metrics["records"] += x.shape[0]
                    driver_state["neval"] += 1
                    opt_state = self._maybe_hooks(driver_state, params,
                                                  model_state, opt_state,
                                                  ahead=ahead)
                    if self.end_when(driver_state):
                        break
                    t_data = time.time()
            t_tail = time.time()
            ahead.drain_all()   # catch up before epoch-boundary hooks
            self.metrics["step_time"] += time.time() - t_tail
            driver_state["epoch_finished"] = True
            opt_state = self._maybe_hooks(driver_state, params, model_state,
                                          opt_state)
            logger.info("Epoch %d done (%d records in %.1fs)",
                        driver_state["epoch"], records, time.time() - t_epoch)
            driver_state["epoch"] += 1
            opt_state = {**opt_state, "epoch": jnp.asarray(
                driver_state["epoch"], jnp.int32)}
            t_epoch = time.time()

        model.params, model.state = params, model_state
        model.grad_params = tree_zeros_like(params)
        self._opt_state = opt_state
        self._join_checkpoint()
        return model

    def _superbatch_epoch(self, ds, loop_fn, ahead, driver_state,
                          params, model_state, opt_state, rng):
        """One epoch in ``steps_per_loop`` mode: superbatches are stacked
        on the Prefetch producer thread (ToSuperBatch), transferred
        double-buffered (DeviceFeed), and each consumed by one (or, when a
        trigger boundary falls mid-superbatch, a few truncated) fused
        K-step dispatches. Returns the advanced
        (params, model_state, opt_state, rng, records)."""
        from bigdl_tpu.dataset.transformer import (DeviceFeed, Prefetch,
                                                   ToSuperBatch)

        def put(sb):
            return jnp.asarray(sb.input), jnp.asarray(sb.target)

        feed = DeviceFeed(put)(Prefetch(2)(
            ToSuperBatch(self.steps_per_loop)(ds.data(train=True))))
        records = 0
        t_data = time.time()
        for sb, (xs, ys) in feed:
            if self.accumulate_steps > 1 \
                    and xs.shape[1] % self.accumulate_steps:
                raise ValueError(
                    f"accumulate_steps={self.accumulate_steps} must "
                    f"divide the batch rows ({xs.shape[1]}); keep "
                    "SampleToMiniBatch's default pad_last=True, or "
                    "set drop_last=True")
            rng, subs = _split_chain(rng, sb.k)
            start = 0
            while start < sb.k:
                j = self._plan_chunk(driver_state, sb.k - start)
                if start == 0 and j == sb.k:
                    cr, cx, cy = subs, xs, ys
                else:
                    sl = slice(start, start + j)
                    cr, cx, cy = subs[sl], xs[sl], ys[sl]
                t0 = time.time()
                self.metrics["data_time"] += t0 - t_data
                obs.record_span("train/feed", t_data, t0,
                                neval=driver_state["neval"])
                fault_point("train.step", neval=driver_state["neval"])
                with obs.span("train/dispatch",
                              neval=driver_state["neval"], k=j):
                    params, model_state, opt_state, losses = loop_fn(
                        params, model_state, opt_state, cr, cx, cy)
                n = sum(sb.sizes[start:start + j])
                ahead.push(losses, n, t0, k=j)
                records += n
                self.metrics["steps"] += j
                self.metrics["dispatches"] += 1
                self.metrics["step_time"] += time.time() - t0
                self.metrics["records"] += n
                driver_state["neval"] += j
                opt_state = self._maybe_hooks(driver_state, params,
                                              model_state, opt_state,
                                              ahead=ahead)
                if self.end_when(driver_state):
                    return params, model_state, opt_state, rng, records
                start += j
                t_data = time.time()
        return params, model_state, opt_state, rng, records

    def _maybe_hooks(self, driver_state, params, model_state, opt_state,
                     ahead=None):
        self._opt_state = opt_state

        def preempt_save():
            self.model.params, self.model.state = params, model_state
            self._checkpoint(driver_state["neval"])

        self._check_preempt(driver_state, ahead, preempt_save)
        # decide which hooks fire BEFORE draining (triggers are stateless
        # predicates over neval/epoch, but deciding once keeps loss-based
        # ones consistent), then catch the pipelined loss readout up:
        # hooks read driver_state, and without the drain its "loss" (and
        # the Loss summary scalars) lag `depth` dispatches behind the
        # neval being validated/checkpointed
        do_val = (self.validation_trigger is not None
                  and self.validation_trigger(driver_state))
        do_ckpt = (self.checkpoint_trigger is not None
                   and self.checkpoint_trigger(driver_state))
        ts = self.train_summary
        hist_trig = getattr(ts, "_summary_trigger", {}).get("Parameters") \
            if ts is not None else None
        do_hist = hist_trig is not None and hist_trig(driver_state)
        if ahead is not None and (do_val or do_ckpt or do_hist):
            ahead.drain_all()
        if do_val:
            with obs.span("train/validate", neval=driver_state["neval"]):
                results = self._validate(params, model_state)
            if results:
                first = next(iter(results.values()))
                driver_state["score"] = first
                opt_state = self._record_plateau(first, opt_state)
                self._opt_state = opt_state
                if self.validation_summary is not None:
                    for name, v in results.items():
                        self.validation_summary.add_scalar(
                            name, v, driver_state["neval"])
        if do_ckpt:
            self.model.params, self.model.state = params, model_state
            with obs.span("train/checkpoint", neval=driver_state["neval"]):
                self._checkpoint(driver_state["neval"])
        if do_hist:
            self._maybe_parameter_histograms(driver_state, params)
        return opt_state

    def _maybe_parameter_histograms(self, driver_state, params):
        """Parameters histograms on their summary trigger (reference
        ``TrainSummary.setSummaryTrigger("Parameters", ...)`` written at
        ``DistriOptimizer.scala:538-569``)."""
        ts = self.train_summary
        trig = getattr(ts, "_summary_trigger", {}).get("Parameters") \
            if ts is not None else None
        if trig is None or not trig(driver_state):
            return
        import numpy as np
        from jax.flatten_util import ravel_pytree
        flat, _ = ravel_pytree(params)
        ts.add_histogram("Parameters", np.asarray(flat),
                         driver_state["neval"])
