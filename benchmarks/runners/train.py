"""Runner for a training job: the program's optimizer loop over a seeded
in-memory feed, in one process.

The configuration names the program's model constructor, criterion, optim
method, optimizer, mesh factory and batch assembler by dotted path with
their keyword arguments; this file knows only their public surface: a
model with ``setup``/``build``/``params``, ``Optimizer(model, dataset,
criterion, mesh=, compute_dtype=)`` with ``set_optim_method``,
``set_end_when``, ``set_train_summary``, ``optimize()`` and ``metrics``,
the ``Trigger``/``Transformer``/``LocalDataSet`` base classes, and the
``Loss`` scalars and ``Parameters`` histogram a train summary is handed.

ONE ``optimize()`` call does everything: its first steps compile and warm
the loop (set-up), the window opens at a step boundary once
``warm_steps`` are done, and a ``Trigger`` of the benchmark's own, which
reads the clock, ends the call once the window has closed. A step counts
as completed when its loss has been read back on the host.

``correct`` compares that same call's first steps: the batches the feed
really produced for them are kept, and once the window has closed and the
program's state is freed the plain reference follows them from the same
weights (made from the seed by the benchmark). Compared: each step's loss,
the first gradient as the optimizer got it (from its velocity after one
step) and the parameters' change after the last checked step, both by the
worst leaf's gap between the two norms.
"""

from __future__ import annotations

import importlib
import threading
import time

import numpy as np

from benchmarks.harness import resolve, sleep_until, weights


TRACE_ENDS_BEFORE_S = 0.25       # the trace stops this long before the window does


def _dtypes(kwargs):
    """Keyword arguments with every ``*_dtype`` name turned into the type."""
    import jax.numpy as jnp
    return {k: jnp.dtype(v) if k.endswith("_dtype") and isinstance(v, str)
            else v for k, v in kwargs.items()}


class _Summary:
    """What the optimizer's loop is handed as its train summary: keeps
    every step's loss with the time it was read back, and the flat
    parameters on the one step the check wants them."""

    def __init__(self, params_at, trigger_base):
        self.loss, self.done_at, self.params = {}, {}, None

        class At(trigger_base):
            def __call__(self, state):
                return int(state.get("neval", 0)) == params_at

        self._summary_trigger = {"Parameters": At()}

    def add_scalar(self, name, value, step):
        if name == "Loss":
            self.loss[int(step)] = float(value)
            self.done_at[int(step)] = time.perf_counter()

    def add_histogram(self, name, values, step):
        if name == "Parameters":
            self.params = np.array(values, np.float32)

    def close(self):
        pass


class Runner:
    fault = None                    # tests plant "half_batch" here

    def __init__(self, ctx):
        self.ctx = ctx
        self.opt = None
        self.kept = []              # the first batches as the feed made them
        self.first_velocity = None

    # -------------------------------------------------------------- set-up --
    def setup(self):
        import jax
        import jax.numpy as jnp
        cfg, ctx = self.ctx.config, self.ctx
        feed = cfg["feed"]
        chips = int(ctx.chips)
        self.batch = int(feed["batch_per_chip"]) * chips
        self.check_steps = int(cfg["check"]["steps"])
        crop, hw = int(feed["crop"]), int(feed["image_hw"])

        self.model = resolve(cfg["constructor"])(**cfg["constructor_kwargs"])
        spec = jax.ShapeDtypeStruct((self.batch, crop, crop, 3), jnp.float32)
        self.shapes, state = jax.eval_shape(
            lambda k: self.model.setup(k, spec), jax.random.key(0))
        ctx.mark("model_shapes")
        # the program's own initialiser would draw 161 leaves one by one;
        # its running statistics (not read in training) start at 0 and 1
        self.model.params = weights.make_params(self.shapes, ctx.seed,
                                                cfg["weights"])
        self.model.state = jax.tree_util.tree_map_with_path(
            lambda path, leaf: (jnp.ones if "var" in str(path[-1])
                                else jnp.zeros)(leaf.shape, leaf.dtype), state)
        jax.block_until_ready(self.model.params)
        ctx.mark("weights")

        rng = np.random.default_rng([int(ctx.seed), 0x1496])
        n = int(feed["images"])
        images = rng.integers(0, 256, (n, hw, hw, 3), np.uint8)
        labels = rng.integers(0, int(feed["classes"]), n)
        dataset = self._dataset(images, labels, int(feed["epoch_batches"]))
        assemble = resolve(feed["assembler"])(
            crop, crop, self.batch, seed=int(ctx.seed),
            **feed["assembler_kwargs"])
        prefetch = resolve(feed["prefetch"])(int(feed["prefetch_depth"]))
        dataset = dataset >> assemble >> self._tap() >> prefetch
        ctx.mark("feed")

        devices = jax.devices()[:chips]
        mesh = resolve(cfg["mesh"])(devices=devices)
        self.opt = resolve(cfg["optimizer"])(
            self.model, dataset, resolve(cfg["criterion"])(), mesh=mesh,
            **_dtypes(cfg["optimizer_kwargs"]))
        self.opt.set_optim_method(
            resolve(cfg["optim_method"])(**cfg["optim_kwargs"]))
        trigger_base = resolve(cfg["trigger_base"])
        self.summary = _Summary(self.check_steps + 1, trigger_base)
        self.opt.set_train_summary(self.summary)
        self.clock = self._clock(trigger_base)
        self.opt.set_end_when(self.clock)
        ctx.mark("optimizer")

    def _dataset(self, images, labels, epoch_batches):
        """The program's in-memory data set over the seeded images, with
        its shuffle seeded too (the program's own draws from the system's
        entropy), and ordered so that no batch holds an image twice."""
        base = resolve(self.ctx.config["feed"]["dataset_base"])
        sample = resolve(self.ctx.config["feed"]["sample"])
        seed, batch = int(self.ctx.seed), self.batch
        n = len(images) - len(images) % batch
        rounds = max(1, -(-epoch_batches * batch // n))

        class Seeded(base):
            epoch = 0

            def shuffle(self, seed_=None):
                self.epoch += 1
                rng = np.random.default_rng([seed, 0x5eed, self.epoch])
                self._order = np.concatenate(
                    [rng.permutation(n) for _ in range(rounds)])
                return self

        return Seeded([sample(images[i], np.float32(labels[i]))
                       for i in range(n)])

    def _tap(self):
        """Between the batch assembler and the prefetch queue: keeps a copy
        of the first batches of the training pass as the feed made them."""
        runner = self
        base = resolve(self.ctx.config["feed"]["transformer_base"])

        class Tap(base):
            calls = 0

            def apply(self, iterator):
                # the optimizer first draws one batch of an untrained pass
                # to size the model; the training pass is the second
                Tap.calls += 1
                training = Tap.calls == 2
                for batch in iterator:
                    if training and len(runner.kept) < runner.check_steps:
                        runner.kept.append(
                            (np.array(batch.get_input(), np.float32),
                             np.array(batch.get_target(), np.float32)))
                        if runner.fault == "half_batch":
                            half = batch.size() // 2
                            batch.get_input()[half:] = batch.get_input()[:half]
                            batch.get_target()[half:] = batch.get_target()[:half]
                    yield batch

        return Tap()

    def _clock(self, trigger_base):
        runner = self

        class Clock(trigger_base):
            """Ends ``optimize()`` by the clock. Called once a step, right
            after the step was dispatched and the one before it read back."""

            def __init__(self):
                self.start = self.end = None
                self.at_start = self.at_end = None

            def __call__(self, state):
                now = time.perf_counter()
                done = int(state.get("neval", 1)) - 1     # steps dispatched
                opt = runner.opt
                if done == 1 and runner.first_velocity is None:
                    runner.first_velocity = np.array(
                        opt._opt_state["velocity"], np.float32)
                if self.start is None:
                    if done >= runner.warm_steps:
                        self.start = now
                        self.end = now + runner.ctx.seconds
                        self.at_start = dict(opt.metrics)
                        runner.window_open.set()
                    return False
                if now >= self.end:
                    self.at_end = dict(opt.metrics)
                    return True
                return False

        return Clock()

    # -------------------------------------------------------------- window --
    def run_window(self, profiler=None):
        ctx = self.ctx
        self.warm_steps = max(int(ctx.traffic.get("warm_steps", 0)),
                              self.check_steps + 2)
        self.window_open = threading.Event()
        tracer = None
        if profiler is not None:
            tracer = threading.Thread(target=self._trace, args=(profiler,),
                                      name="bench-trace", daemon=True)
            tracer.start()
        self.error = None
        try:
            self.opt.optimize()
        except Exception as e:          # a step that failed: not correct
            import traceback
            traceback.print_exc()
            self.error = e
        self.window_open.set()
        if tracer is not None:
            tracer.join()
        clock = self.clock
        if clock.start is None:          # never got through its warm-up
            now = time.perf_counter()
            clock.start, clock.end = now, now + ctx.seconds
        ctx.window = (clock.start, clock.end)
        ctx.phases.append(("first_steps", clock.start))
        ctx.steps = sorted((t, n) for n, t in self.summary.done_at.items())
        ctx.counters["batch"] = self.batch
        if clock.at_start and clock.at_end:
            for k in ("data_time", "step_time", "steps", "records"):
                ctx.counters["window_" + k] = (clock.at_end[k]
                                               - clock.at_start[k])
        return ctx.window

    def _trace(self, profiler):
        """Traces the seconds before the window's last step and stops while
        the loop still runs, so that what ``optimize()`` does once it has
        ended (it reads the parameters back leaf by leaf) is not in the
        trace. The runtime's host events are off: with them on, each step
        of this loop took 0.9 s on the host instead of 0.1 s for as long as
        the trace ran, the traced part showed a device 80 % idle, and the
        profiler took 150 s to stop (PERF.md section 3)."""
        self.window_open.wait()
        if self.clock.start is None:
            return
        a, b = profiler.plan(self.clock.start, self.clock.end)
        sleep_until(self.clock.end - TRACE_ENDS_BEFORE_S - (b - a))
        profiler.start(host_tracer_level=0)
        sleep_until(self.clock.end - TRACE_ENDS_BEFORE_S)
        self.ctx.traced = profiler.interval_so_far()
        profiler.stop()
        self.ctx.notes["trace_stop_s"] = (time.perf_counter()
                                          - self.ctx.traced[1])

    def release(self):
        """Free the program's state, so the reference runs in an empty chip
        and after the peak was read."""
        self.opt = self.model = None

    # ------------------------------------------------------------- correct --
    def verify(self, with_control=False):
        """``(attempted, failed, checks, controls)``: the numbers compared,
        ``{"name", "value", "limit"}``, and, ``with_control`` only, the same
        numbers as each control and each planted fault reads them, put in
        the program's place."""
        import jax
        import jax.numpy as jnp
        from jax.flatten_util import ravel_pytree
        ctx, cfg = self.ctx, self.ctx.config
        lim, steps = cfg["check"], self.check_steps
        done = [t for t, _ in getattr(ctx, "steps", ())
                if ctx.window[0] <= t < ctx.window[1]]
        attempted = max(len(done), 1)
        losses = self.summary.loss
        bad = sum(1 for n, v in losses.items() if not np.isfinite(v))
        failed = bad + (1 if self.error is not None else 0)
        have = (len(self.kept) >= steps and self.first_velocity is not None
                and self.summary.params is not None
                and all(n in losses for n in range(1, steps + 1)))
        checks = [{"name": "failed_steps", "value": failed, "limit": 0},
                  {"name": "checked_steps_short_of",
                   "value": 0 if have else steps, "limit": 0}]
        if not have:
            return attempted, failed, checks, {}

        ref_mod = importlib.import_module(
            "benchmarks.reference." + cfg["reference"])
        reference, others = ref_mod.make(cfg)
        params = weights.make_params(self.shapes, ctx.seed, cfg["weights"])
        flat0, unravel = ravel_pytree(params)
        n = flat0.shape[0]
        ctx.mark("verify_weights")
        batches = [(jnp.asarray(x), jnp.asarray(y)) for x, y in self.kept]
        ref_losses, ref_grad, ref_change = reference(params, batches)
        ctx.mark("verify_reference")

        wd = float(cfg["optim_kwargs"].get("weightdecay", 0.0))
        mom = float(cfg["optim_kwargs"].get("momentum", 0.0))
        damp = float(cfg["optim_kwargs"].get("dampening", mom))
        # v1 = (1 - dampening) * (g1 + weightdecay * w0)
        grad = unravel(jnp.asarray(self.first_velocity[:n]) / (1.0 - damp)
                       - wd * flat0)
        change = unravel(jnp.asarray(self.summary.params[:n]) - flat0)
        g_ref = _norms(ref_grad)
        moved = g_ref >= 1e-3 * np.median(g_ref)
        prog = ([losses[i] for i in range(1, steps + 1)], grad, change)
        base = (ref_losses, ref_grad, ref_change)
        worst = {}
        for name, value in _gaps(prog, base, moved, worst).items():
            checks.append({"name": name, "value": value,
                           "limit": lim.get(name)})
        ctx.notes["checked"] = {
            "steps": steps, "rows": int(self.kept[0][0].shape[0]),
            "rows_twice": int(sum(len(x) - len(np.unique(
                x.reshape(len(x), -1)[:, :4096], axis=0))
                for x, _ in self.kept)),
            "leaves": int(len(g_ref)), "leaves_left_out": int((~moved).sum()),
            "reference_losses": ref_losses, "worst_leaf": worst}
        controls = {}
        if with_control:
            for name, follow in others.items():
                gaps = _gaps(follow(params, batches), base, moved, worst, name)
                controls[name] = [{"name": k, "value": v, "limit": lim.get(k)}
                                  for k, v in gaps.items()]
            ctx.mark("verify_controls")
        del params, batches, ref_grad, ref_change
        jax.clear_caches()
        return attempted, failed, checks, controls


def _norms(tree):
    """The norm of every leaf, read back in one transfer."""
    import jax
    import jax.numpy as jnp
    return np.asarray(jnp.stack(
        [jnp.linalg.norm(leaf.astype(jnp.float32))
         for leaf in jax.tree_util.tree_leaves(tree)]), np.float64)


def _leaf_gaps(tree, ref_tree, keep):
    """Over the leaves kept, the gap between this side's norm of a leaf and
    the reference's, against the reference's norm of that leaf or of its
    median leaf, whichever is larger: ``(widest, median, which)``, where
    ``which`` says which leaf read widest: its path and shape and the two
    norms over the reference's median leaf's."""
    import jax
    a, b = _norms(tree), _norms(ref_tree)
    gap = np.where(keep, np.abs(a - b) / np.maximum(b, np.median(b)), -1.0)
    i = int(np.argmax(gap))
    path, leaf = jax.tree_util.tree_flatten_with_path(ref_tree)[0][i]
    which = {"leaf": jax.tree_util.keystr(path), "shape": list(leaf.shape),
             "norm_over_median": round(float(a[i] / np.median(b)), 4),
             "reference_over_median": round(float(b[i] / np.median(b)), 4)}
    return float(gap[i]), float(np.median(gap[keep])), which


def _gaps(side, base, moved, worst, prefix=""):
    """The numbers compared, by name: ``side`` and ``base`` are ``(losses,
    first gradient, change)``; ``moved`` marks the leaves whose reference
    gradient is not nought to rounding (the others are left out of the
    change). ``worst`` is filled with which leaf read widest in each."""
    losses, grad, change = side
    ref_losses, ref_grad, ref_change = base
    out = {f"loss_gap.step{i + 1}": abs(a - b) / abs(b)
           for i, (a, b) in enumerate(zip(losses, ref_losses))}
    every = np.ones(len(moved), bool)
    for name, tree, ref, keep in (("first_gradient_gap", grad, ref_grad, every),
                                  ("change_gap", change, ref_change, moved)):
        out[name], out[name + ".median_leaf"], which = _leaf_gaps(tree, ref, keep)
        worst[(prefix and prefix + ".") + name] = which
    return out
