"""AllReduceParameter: the XLA-collective re-design of the reference's
block-manager allreduce.

Reference: ``parameters/AllReduceParameter.scala:78``. There, the flattened
model vector of size N is cut into P contiguous slices; executor p owns
slice p:
  - weights:     each owner holds its f32 ``weightPartition``; every iteration
                 all executors pull all P slices fp16-compressed
                 (``getWeights:181``) -> an all-gather in wire precision.
  - gradients:   every executor cuts its local gradient into P slices and
                 publishes them fp16; slice owners pull + tree-add
                 (``putGradients/aggregateGradientPartition``)
                 -> a reduce-scatter in wire precision.
  - update:      the owner runs the OptimMethod on its f32 slice only
                 (``DistriOptimizer.scala:374``) -> optimizer state sharded
                 by slice (ZeRO-1).

TPU-natively both transfers are single XLA collectives riding the ICI mesh
inside one jitted step, and the master weights stay *sharded* in f32 (each
device materialises only its own slice — the fp16/bf16 rounding only ever
touches the wire copies used for compute, never the master accumulator):

    weight_shard (f32, P(axis))
      --all_gather(wire_dtype)-->  full weights (bf16 copy)  -> fwd/bwd
    flat_grad    --psum_scatter(wire_dtype)--> my grad slice (mean)
    weight_shard --OptimMethod.update (slice-sharded opt state)--> new shard

No host round-trip, no 2-jobs-per-iteration: XLA fuses forward, backward,
both collectives and the update into one program (SURVEY.md section 2.6).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.flatten_util import ravel_pytree
from jax.sharding import NamedSharding, PartitionSpec as P


def ring_allreduce_bytes(n_elems, ndev, dtype=jnp.bfloat16):
    """Wire bytes per device for one ring allreduce of ``n_elems`` elements
    (reduce-scatter + all-gather each move (n-1)/n of the vector)."""
    return int(2 * (ndev - 1) / ndev * n_elems * jnp.dtype(dtype).itemsize)


def record_allreduce(n_bytes, seconds=None):
    """Publish one allreduce's wire traffic (and, when the caller timed a
    blocking sync, its duration) on the obs default registry:
    ``bigdl_allreduce_bytes_total`` and ``bigdl_allreduce_sync_seconds``.
    Called per dispatch from the distributed loops (bytes are the
    analytic ring cost — collectives run inside the fused step, so
    per-collective host timing does not exist there) and from
    :func:`allreduce_bandwidth` (which does block, so it has real
    seconds)."""
    from bigdl_tpu import obs
    from bigdl_tpu.resilience.faults import fault_point
    # injection site for collective-sync failures: called per dispatch
    # from inside the distributed retry loop, so an injected error here
    # exercises the same reload-and-rebuild path a real ICI fault takes
    fault_point("allreduce.sync", n_bytes=n_bytes)
    obs.counter("bigdl_allreduce_bytes_total",
                "wire bytes moved by gradient allreduce").inc(n_bytes)
    if seconds is not None:
        obs.histogram("bigdl_allreduce_sync_seconds",
                      "blocking allreduce sync time").observe(seconds)


def _pad_to_multiple(vec, multiple):
    pad = (-vec.shape[0]) % multiple
    if pad:
        vec = jnp.concatenate([vec, jnp.zeros((pad,), vec.dtype)])
    return vec, pad


class AllReduceParameter:
    """Slice-owned flat parameter view (API parity with
    ``AllReduceParameter.scala``; the collectives live in
    :func:`make_distributed_train_step`)."""

    def __init__(self, params, n_partitions, wire_dtype=jnp.bfloat16):
        self.n_partitions = n_partitions
        self.wire_dtype = wire_dtype
        flat, self.unravel = ravel_pytree(params)
        self.total_size = flat.shape[0]
        padded, self.padding = _pad_to_multiple(flat, n_partitions)
        self.padded_size = padded.shape[0]
        self.slice_size = self.padded_size // n_partitions
        self._flat = padded

    def flat(self):
        return self._flat

    def to_params(self, flat):
        return self.unravel(flat[:self.total_size])

    def slice_of(self, flat, pid):
        return lax.dynamic_slice_in_dim(flat, pid * self.slice_size,
                                        self.slice_size)


def make_distributed_train_step(module, criterion, optim_method, mesh,
                                axis="data", clipping=None,
                                wire_dtype=jnp.bfloat16,
                                compute_dtype=None,
                                donate=True, accumulate_steps=1):
    """Build the multi-chip data-parallel train step.

    Returns a factory: ``factory(params) -> (step_fn, weight_shard,
    opt_shard)`` where both ``weight_shard`` (f32 master, P(axis)) and
    ``opt_shard`` (optimizer slots on the owned slice — ZeRO-1) are sharded
    along the mesh axis, and

    ``step_fn(weight_shard, model_state, opt_shard, rng, x, y) ->
    (weight_shard, model_state, opt_shard, loss)``

    is one jitted program containing all_gather + forward + backward +
    reduce_scatter + sharded update. ``x``/``y`` must be sharded along dim 0
    over ``axis``. ``clipping``: None | ("constant", lo, hi) |
    ("l2norm", max_norm).

    ``accumulate_steps=K`` runs the forward/backward K times over
    micro-batches via ``lax.scan`` inside the SAME jitted step: K× the
    effective batch at 1× activation memory (XLA reuses the micro-batch
    buffers across scan iterations), with weights gathered once and ONE
    reduce-scatter + update per step. K must divide each
    device's local batch rows. Gradients/loss are f32 means over micro-batches, so for
    mean-reduction criteria the result equals the single big-batch step
    (stateful layers like BN see micro-batches sequentially — same as the
    reference's per-core mini-batch statistics).

    The returned ``step_fn`` also carries ``step_fn.train_loop`` — the
    ``steps_per_loop`` fused loop: ``(weight_shard, model_state,
    opt_shard, rngs[K], xs[K, ...], ys[K, ...]) -> (..., losses[K])``,
    K full steps scanned inside one jitted dispatch (the TPU
    ``steps_per_loop`` idiom; see ``optim.optimizer.make_train_loop``
    for the single-device twin).
    """
    ndev = mesh.shape[axis]
    arp_holder = {}

    def _cast(tree, dtype):
        return jax.tree_util.tree_map(
            lambda v: v.astype(dtype)
            if jnp.issubdtype(v.dtype, jnp.floating) else v, tree)

    def init_fn(params):
        arp = AllReduceParameter(params, ndev, wire_dtype)
        arp_holder["arp"] = arp
        opt_spec = _opt_specs(optim_method, arp, axis)
        # each device initialises master weights + optimizer slots for its
        # OWN slice only (ZeRO-1; reference: parameters.init publishes the
        # owned slice, AllReduceParameter.scala:137)
        shard_opt_init = shard_map(
            lambda flat_local: optim_method.init_state(flat_local),
            mesh=mesh, in_specs=P(axis), out_specs=opt_spec, check_vma=False)
        flat = jax.device_put(arp.flat(), NamedSharding(mesh, P(axis)))
        opt_shard = shard_opt_init(flat)
        return flat, opt_shard

    # gradient multipliers for freeze()/setScaleW (flattened once, static)
    def _flat_scales(params):
        scales = module.grad_scale_tree(params)
        if all(s == 1.0 for s in jax.tree_util.tree_leaves(scales)):
            return None
        full = jax.tree_util.tree_map(
            lambda p, s: jnp.full(p.shape, s, jnp.float32), params, scales)
        flat, _ = ravel_pytree(full)
        flat, _ = _pad_to_multiple(flat, ndev)
        return flat

    def _loss_and_grads(params, model_state, rng, x, y):
        def loss_fn(p):
            inp = x
            if compute_dtype is not None:
                inp = _cast(inp, compute_dtype)
                p = _cast(p, compute_dtype)
            out, new_state = module.apply(p, model_state, inp,
                                          training=True, rng=rng)
            out = jax.tree_util.tree_map(
                lambda v: v.astype(jnp.float32)
                if jnp.issubdtype(v.dtype, jnp.floating) else v, out)
            loss = criterion.apply(out, y) + module.regularization_loss(p)
            return loss, new_state

        return jax.value_and_grad(loss_fn, has_aux=True)(params)

    def make_step(params):
        arp = arp_holder["arp"]
        flat_scales = _flat_scales(params)

        def local_step(weight_shard, model_state, opt_shard, rng, x, y):
            # per-device program; collectives are explicit
            idx = lax.axis_index(axis)
            rng = jax.random.fold_in(rng, idx)
            # --- all-gather weights in wire dtype (reference: getWeights
            # pulls fp16-compressed slices, AllReduceParameter.scala:181) ---
            full = lax.all_gather(weight_shard.astype(wire_dtype), axis,
                                  tiled=True).astype(jnp.float32)
            params_now = arp.to_params(full)
            if accumulate_steps > 1:
                from bigdl_tpu.optim.optimizer import scan_microbatches

                def micro_fn(state, mrng, mx, my):
                    (mloss, new_state), grads = _loss_and_grads(
                        params_now, state, mrng, mx, my)
                    flat_g, _ = ravel_pytree(grads)
                    flat_g, _ = _pad_to_multiple(flat_g, ndev)
                    return mloss, new_state, flat_g

                flat_grad, loss, new_model_state = scan_microbatches(
                    accumulate_steps, rng, x, y, micro_fn,
                    jnp.zeros((arp.padded_size,), jnp.float32),
                    combine=jnp.add)(model_state)
            else:
                (loss, new_model_state), grads = _loss_and_grads(
                    params_now, model_state, rng, x, y)
                flat_grad, _ = ravel_pytree(grads)
                flat_grad, _ = _pad_to_multiple(flat_grad, ndev)
            if flat_scales is not None:
                flat_grad = flat_grad * flat_scales
            # --- reduce-scatter gradients in wire dtype (reference:
            # putGradients publishes fp16 blocks, owner tree-adds) ---
            wire = flat_grad.astype(wire_dtype)
            grad_slice = lax.psum_scatter(wire, axis, scatter_dimension=0,
                                          tiled=True)
            grad_slice = grad_slice.astype(jnp.float32) / ndev
            if clipping is not None:
                kind = clipping[0]
                if kind == "constant":
                    grad_slice = jnp.clip(grad_slice, clipping[1], clipping[2])
                elif kind == "l2norm":
                    # global norm needs a psum over the slices
                    sq = lax.psum(jnp.sum(jnp.square(grad_slice)), axis)
                    scale = jnp.minimum(1.0,
                                        clipping[1] / (jnp.sqrt(sq) + 1e-12))
                    grad_slice = grad_slice * scale
                else:
                    raise ValueError(f"unknown clipping {kind}")
            # --- owner updates its f32 master slice (reference:
            # optimMethod.optimize(_, weightPartition)) ---
            new_shard, new_opt = optim_method.update(grad_slice, opt_shard,
                                                     weight_shard)
            # keep replicated buffers bit-identical across devices
            new_model_state = jax.tree_util.tree_map(
                lambda v: lax.pmean(v, axis)
                if jnp.issubdtype(v.dtype, jnp.inexact) else v,
                new_model_state)
            loss = lax.pmean(loss, axis)
            return new_shard, new_model_state, new_opt, loss

        opt_spec = _opt_specs(optim_method, arp, axis)
        # check_vma=False: replicated outputs (pmean) can't be statically
        # proven through the data-dependent slicing
        step = shard_map(
            local_step, mesh=mesh,
            in_specs=(P(axis), P(), opt_spec, P(), P(axis), P(axis)),
            out_specs=(P(axis), P(), opt_spec, P()), check_vma=False)
        donate_argnums = (0, 1, 2) if donate else ()
        # ``jax.jit`` names an executable ``jit_`` + its function's name,
        # and the benchmark finds the train step in the device trace as
        # ``jit_local_step`` (``executables`` in
        # benchmarks/configs/resnet50-imagenet-train.json): named here on
        # purpose, not by way of what ``shard_map`` wraps
        # (tests/test_executable_names.py holds it)
        step.__name__ = "local_step"
        jit_step = jax.jit(step, donate_argnums=donate_argnums)

        def train_loop(weight_shard, model_state, opt_shard, rngs, xs, ys):
            def body(carry, sl):
                w, ms, os_ = carry
                rng, x, y = sl
                w, ms, os_, loss = step(w, ms, os_, rng, x, y)
                return (w, ms, os_), loss

            (w, ms, os_), losses = lax.scan(
                body, (weight_shard, model_state, opt_shard), (rngs, xs, ys))
            return w, ms, os_, losses

        # steps_per_loop: K full distributed steps — each with its own
        # all_gather + fwd/bwd (+ accumulate_steps micro-scan) +
        # psum_scatter + ZeRO-1 sharded update — fused into ONE jitted
        # lax.scan over a stacked [K, batch, ...] superbatch (xs/ys
        # sharded P(None, axis); per-step losses come back stacked [K]).
        # Master shard / model_state / opt slots are donated across the
        # whole loop. Lazily compiled, one program per distinct K.
        jit_step.train_loop = jax.jit(train_loop,
                                      donate_argnums=donate_argnums)
        return jit_step

    def step_factory(params):
        flat, opt_shard = init_fn(params)
        return make_step(params), flat, opt_shard

    return step_factory


def make_distributed_eval_step(module, methods, mesh, axis="data",
                               wire_dtype=jnp.bfloat16, compute_dtype=None):
    """In-mesh validation: ONE jitted program per batch — all_gather the
    sharded master weights in wire dtype, sharded forward over ``axis``,
    then psum each ``ValidationMethod``'s (value, count) counters. Weights
    never materialize to host (reference ``optim/DistriValidator.scala:35``
    validates in place across executors instead of collecting the model).

    Returns ``factory(params) -> eval_fn`` with
    ``eval_fn(weight_shard, model_state, x, y, valid) ->
    ((value, count), ...)`` (replicated scalars, one pair per method,
    dataset-mergeable by the ValidationResult algebra). ``valid`` is a
    per-sample bool vector sharded like the batch: padded tail rows are
    masked out of the psum'd counters so a dataset whose size does not
    divide the batch still yields exact counts (reference
    ``optim/DistriValidator.scala:25``). The returned fn carries
    ``supports_valid``: False when a custom ValidationMethod still has the
    two-argument ``counters`` signature, in which case the mask is ignored
    and the caller must skip padded batches.
    """
    import inspect

    ndev = mesh.shape[axis]

    def _accepts_valid(m):
        try:
            return "valid" in inspect.signature(m.counters).parameters
        except (TypeError, ValueError):
            return False

    supports_valid = all(_accepts_valid(m) for m in methods)

    def _cast(tree, dtype):
        return jax.tree_util.tree_map(
            lambda v: v.astype(dtype)
            if jnp.issubdtype(v.dtype, jnp.floating) else v, tree)

    def factory(params):
        arp = AllReduceParameter(params, ndev, wire_dtype)

        def local_eval(weight_shard, model_state, x, y, valid):
            full = lax.all_gather(weight_shard.astype(wire_dtype), axis,
                                  tiled=True).astype(jnp.float32)
            p = arp.to_params(full)
            inp = x
            if compute_dtype is not None:
                p = _cast(p, compute_dtype)
                inp = _cast(inp, compute_dtype)
            out, _ = module.apply(p, model_state, inp, training=False)
            out = jax.tree_util.tree_map(
                lambda v: v.astype(jnp.float32)
                if jnp.issubdtype(v.dtype, jnp.floating) else v, out)
            res = []
            for m in methods:
                if supports_valid:
                    v, c = m.counters(out, y, valid=valid)
                else:
                    v, c = m.counters(out, y)
                res.append((lax.psum(jnp.asarray(v, jnp.float32), axis),
                            lax.psum(jnp.asarray(c, jnp.float32), axis)))
            return tuple(res)

        step = shard_map(
            local_eval, mesh=mesh,
            in_specs=(P(axis), P(), P(axis), P(axis), P(axis)),
            out_specs=P(), check_vma=False)
        # eval step: the same weight shards / model state feed every
        # validation batch, so none of the arguments may be donated
        # (re-reviewed 2026-08-05 for the jaxlint v2 interprocedural
        # rules: still required — every eval batch re-feeds these shards)
        # jaxlint: disable-next-line=missing-donation
        fn = jax.jit(step)
        fn.supports_valid = supports_valid
        return fn

    return factory


def _opt_specs(optim_method, arp, axis):
    struct = jax.eval_shape(
        lambda: optim_method.init_state(
            jnp.zeros((arp.slice_size,), jnp.float32)))
    # scalar counters (step/epoch) replicate; per-parameter slots shard
    return jax.tree_util.tree_map(
        lambda s: P(axis) if s.ndim > 0 else P(), struct)


def allreduce_bandwidth(mesh, size_mb=64, axis="data", dtype=jnp.bfloat16,
                        iters=10, pattern="step"):
    """Measure collective bus bandwidth over the mesh — the
    instrumentation the BASELINE asks for (reference measured phase times
    via Spark accumulators, ``optim/Metrics.scala:103``).

    ``pattern="step"`` (default) times the EXACT pair the distributed
    train step issues — ``all_gather`` of the wire-dtype weight shards
    plus ``psum_scatter`` of the full wire-dtype gradient
    (``local_step`` above) — in one jitted program, so the efficiency
    number describes what training actually runs. ``pattern="psum"``
    times the plain allreduce primitive for comparison. In ring terms
    both move the same bytes: allreduce = reduce-scatter + all-gather,
    each shifting (n-1)/n of the vector per device.
    """
    import time
    n = int(size_mb * 1024 * 1024 / jnp.dtype(dtype).itemsize)
    ndev = mesh.shape[axis]
    n -= n % ndev

    if pattern == "step":
        def f(w_shard, g_full):
            full = lax.all_gather(w_shard, axis, tiled=True)
            # the real step computes fwd/bwd between the two collectives,
            # so they are strictly ordered; without this barrier XLA may
            # overlap the independent rings and report >100% of the
            # one-direction peak
            full, g_full = lax.optimization_barrier((full, g_full))
            g_slice = lax.psum_scatter(g_full, axis, scatter_dimension=0,
                                       tiled=True)
            # consume both results so neither collective is dead code
            return full[:1] + g_slice[:1]

        fn = jax.jit(shard_map(f, mesh=mesh, in_specs=(P(axis), P()),
                                   out_specs=P(axis), check_vma=False))
        w = jax.device_put(jnp.ones((n,), dtype),
                           NamedSharding(mesh, P(axis)))
        # pre-replicated (each device reduces a full-length local
        # gradient): a plain host array would re-broadcast inside the
        # timed loop and pollute the measurement
        g = jax.device_put(jnp.ones((n,), dtype),
                           NamedSharding(mesh, P()))
        args = (w, g)
    elif pattern == "psum":
        def f(x):
            return lax.psum(x, axis)

        fn = jax.jit(shard_map(f, mesh=mesh, in_specs=P(),
                                   out_specs=P(), check_vma=False))
        args = (jax.device_put(jnp.ones((n,), dtype),
                               NamedSharding(mesh, P())),)
    else:
        raise ValueError(f"unknown pattern {pattern!r}")

    fn(*args).block_until_ready()  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / iters
    bytes_moved = ring_allreduce_bytes(n, ndev, dtype)
    record_allreduce(bytes_moved * iters, seconds=dt)
    out = {"pattern": ("all_gather+psum_scatter (train step)"
                       if pattern == "step" else "psum"),
           "seconds_per_allreduce": dt,
           "algo_bandwidth_gbps": n * jnp.dtype(dtype).itemsize / dt / 1e9,
           "bus_bandwidth_gbps": bytes_moved / dt / 1e9}
    # efficiency vs the link bound (the BASELINE >=90% target)
    peak = ici_peak_gbps()
    if peak:
        out["efficiency_vs_peak"] = out["bus_bandwidth_gbps"] / peak
        out["ici_peak_gbps"] = peak
    return out


# one-direction per-link ICI bandwidth by device generation, GB/s (public
# figures: v4 ~100 GB/s/link/dir, v5e ~50, v5p ~100, v6e ~100; the "How to
# Scale Your Model" roofline numbers). Keyed by device_kind substrings.
_ICI_PEAK_GBPS = (("v6", 100.0), ("v5p", 100.0), ("v5 lite", 50.0),
                  ("v5litepod", 50.0), ("v5e", 50.0), ("v5", 100.0),
                  ("v4", 100.0), ("v3", 70.0), ("v2", 62.5))


def ici_peak_gbps(device_kind=None):
    """Per-link one-direction ICI peak for the running device generation —
    the denominator of the allreduce-efficiency north star. The
    BIGDL_TPU_PEAK_ICI_GBPS flag overrides; unknown kinds (e.g. the CPU
    test mesh) return None so the efficiency field is omitted rather than
    fabricated."""
    from bigdl_tpu.utils.engine import get_flag
    peak = get_flag("BIGDL_TPU_PEAK_ICI_GBPS", None, float)
    if peak:
        return peak
    if device_kind is None:
        dev = jax.devices()[0]
        if dev.platform != "tpu":
            return None
        device_kind = dev.device_kind
    kind = device_kind.lower()
    for sub, gbps in _ICI_PEAK_GBPS:
        if sub in kind:
            return gbps
    return None
