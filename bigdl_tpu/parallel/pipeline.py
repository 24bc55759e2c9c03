"""Pipeline parallelism: GPipe-style microbatching over a mesh axis.

No reference analog (the reference is data-parallel only — SURVEY.md
section 2.6) — this is TPU-native green-field, the "inner loop pipeline"
from the scaling playbook: stages live on the devices of a ``pipe`` mesh
axis, microbatch activations move stage-to-stage with ``lax.ppermute``
inside ONE ``lax.scan`` — a single jitted SPMD program, reverse-mode
differentiable end to end (the vjp of ppermute is the reverse ppermute, the
vjp of scan is a scan), so pipeline-parallel TRAINING works without any
manual schedule.

Constraint (inherent to SPMD): stages must be structurally identical — one
``stage_module`` applied with per-stage params (a transformer block stack
is the canonical fit). Embeddings/heads stay outside the pipeline
(replicated or data-parallel), which is also how production jax/TPU
pipelines are laid out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P


def pipeline_apply(stage_fn, stage_params, xs, axis, n_stages):
    """Per-device body: run the pipeline over microbatches.

    ``stage_fn(params, x) -> y`` with x/y of identical shape;
    ``stage_params``: this device's stage params;
    ``xs``: (n_micro, micro_batch, ...) — the full microbatch stream
    (replicated; only stage 0 reads it).
    Returns (n_micro, micro_batch, ...) outputs valid on the LAST stage.
    """
    n_micro = xs.shape[0]
    d = lax.axis_index(axis)
    ticks = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    state = jnp.zeros_like(xs[0])
    outputs = jnp.zeros_like(xs)

    def tick(carry, t):
        state, outputs = carry
        # stage 0 ingests microbatch t while it exists; other stages (and
        # drained ticks) consume the activation handed over the ring
        x_idx = jnp.clip(t, 0, n_micro - 1)
        x_in = jnp.where(d == 0, xs[x_idx], state)
        y = stage_fn(stage_params, x_in)
        # the LAST stage completed microbatch t - (n_stages - 1) this tick
        out_idx = t - (n_stages - 1)
        write = jnp.logical_and(d == n_stages - 1, out_idx >= 0)
        safe_idx = jnp.clip(out_idx, 0, n_micro - 1)
        outputs = outputs.at[safe_idx].set(
            jnp.where(write, y, outputs[safe_idx]))
        state = lax.ppermute(y, axis, perm)
        return (state, outputs), None

    (_, outputs), _ = lax.scan(tick, (state, outputs),
                               jnp.arange(ticks))
    return outputs


def make_pipeline_train_step(stage_module, criterion, optim_method, mesh,
                             axis="pipe", n_micro=4):
    """Build the pipeline-parallel train step.

    ``stage_module``: ONE stage (e.g. k transformer layers as a module);
    its params are stacked with a leading (n_stages,) dim sharded over
    ``axis``. Input x: (n_micro, micro_batch, ...) replicated; y likewise.
    Loss is computed on the last stage's outputs and psum'd so every
    device returns the same scalar; each device updates only its own
    stage's params (no gradient traffic across stages beyond the
    activation ppermutes — ZeRO-0 pipeline).

    Returns ``factory(stacked_params) -> (step_fn, sharded_params,
    sharded_opt_state)``.
    """
    n_stages = mesh.shape[axis]

    def stage_fn(params, x):
        y, _ = stage_module.apply(params, stage_module.state, x,
                                  training=True)
        return y

    def local_step(stacked_params, opt_state, xs, ys):
        # this device's stage slice (leading dim 1 under shard_map P(axis))
        my = jax.tree_util.tree_map(lambda v: v[0], stacked_params)

        def loss_fn(my_params):
            outs = pipeline_apply(stage_fn, my_params, xs, axis, n_stages)
            loss = criterion.apply(
                outs.reshape((-1,) + outs.shape[2:]),
                ys.reshape((-1,) + ys.shape[2:]))
            # only the last stage's outputs are real. NO psum inside the
            # differentiated function: seeding the replicated psum result
            # on every device would scale gradients by n_stages; the
            # cross-stage cotangents travel through ppermute's transpose
            # on their own.
            is_last = (lax.axis_index(axis) == n_stages - 1)
            return jnp.where(is_last, loss, 0.0)

        loss, grads = jax.value_and_grad(loss_fn)(my)
        loss = lax.psum(loss, axis)  # report the same scalar everywhere
        new_my, new_opt = optim_method.update(grads, opt_state, my)
        new_stacked = jax.tree_util.tree_map(
            lambda v: v[None], new_my)
        return new_stacked, new_opt, loss

    def factory(stacked_params):
        spec = jax.tree_util.tree_map(lambda _: P(axis), stacked_params)
        sharded = jax.device_put(
            stacked_params,
            jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), spec))
        my0 = jax.tree_util.tree_map(lambda v: v[0], stacked_params)
        opt_state = optim_method.init_state(my0)
        opt_spec = jax.tree_util.tree_map(
            lambda v: P() if getattr(v, "ndim", 0) == 0 else P(axis),
            opt_state)
        # per-stage optimizer slots: replicate scalars, shard stage params
        # (each device only ever reads/writes its own stage's slots)
        opt_sharded = jax.device_put(
            jax.tree_util.tree_map(
                lambda v: jnp.broadcast_to(
                    v, (n_stages,) + jnp.shape(v))
                if getattr(v, "ndim", 0) > 0 else v, opt_state),
            jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), opt_spec))

        def wrapped(stacked_params, opt_state, xs, ys):
            my_opt = jax.tree_util.tree_map(
                lambda v: v[0] if getattr(v, "ndim", 0) > 0 else v,
                opt_state)
            new_stacked, new_opt, loss = local_step(stacked_params,
                                                    my_opt, xs, ys)
            new_opt_stacked = jax.tree_util.tree_map(
                lambda v: v[None] if getattr(v, "ndim", 0) > 0 else v,
                new_opt)
            return new_stacked, new_opt_stacked, loss

        step = shard_map(
            wrapped, mesh=mesh,
            in_specs=(spec, opt_spec, P(), P()),
            out_specs=(spec, opt_spec, P()), check_vma=False)
        return jax.jit(step, donate_argnums=(0, 1)), sharded, opt_sharded

    return factory
