"""Runner for a served causal LM: the program's engine behind the general
load generator, in one process.

The configuration names the program's model constructor and engine by
dotted path with their keyword arguments; this file knows only their
public surface: ``engine.submit(prompt, max_new_tokens, temperature=)``
returning an iterable of tokens, ``engine.stats`` (the ``DecodeCounters``),
``engine.shutdown()``, and the program's host spans.

``correct`` compares what the window itself served: once it has closed, a
sample (drawn from the seed; the longest request and one of every prefill
class in it) of the requests it finished is run through the plain
reference, one whole sequence at a time. A greedy token is held by how far
its reference logit lies below the reference's best (the widest gap, and
the mean of the squared gaps); a sampled token by how far it lies below
the floor of the set the reference would draw from under the engine's
``top_k`` and ``top_p``.
"""

from __future__ import annotations

import importlib

import numpy as np

from benchmarks.harness import loadgen, resolve, sleep_until, weights, window

DRAIN_S = 60.0   # seconds the last requests sent may take; ``drain_s`` overrides


class Runner:
    def __init__(self, ctx):
        self.ctx = ctx
        self.engine = None
        self.params = None
        self.gen = None

    # -------------------------------------------------------------- set-up --
    def setup(self):
        import jax
        cfg, ctx = self.ctx.config, self.ctx
        self.model = resolve(cfg["constructor"])(**cfg["constructor_kwargs"])
        shapes = jax.eval_shape(lambda k: self.model.setup(k, None)[0],
                                jax.random.key(0))
        self.shapes = shapes
        ctx.mark("model_shapes")
        self.params = weights.make_params(shapes, ctx.seed, cfg["weights"],
                                          dtype=cfg["dtype"])
        jax.block_until_ready(self.params)
        ctx.mark("weights")
        self.engine = resolve(cfg["engine"])(
            self.model, self.params, seed=int(ctx.seed) & 0x7FFFFFFF,
            **cfg["engine_kwargs"])
        vocab = int(cfg["constructor_kwargs"]["vocab_size"])
        self.vocab = vocab
        ctx.mark("engine")
        self.replan()
        ctx.mark("plan")
        self._warm_up(vocab)
        ctx.mark("warm_up")

    def replan(self):
        ctx = self.ctx
        self.planned = loadgen.plan(ctx.traffic, ctx.seed, ctx.seconds,
                                    self.vocab)

    def _warm_up(self, vocab):
        """One request at the longest prompt of every power-of-two length
        class the plan holds, one after the other (a prefill executable is
        keyed on its bucket alone), the first of them sampled so the
        sampling branch of the step runs once. ``window_compiles`` tells
        when the program's bucketing no longer matches this."""
        longest = {}
        for p in self.planned:
            n = len(p.prompt)
            cls = max(16, 1 << (n - 1).bit_length())
            longest[cls] = max(longest.get(cls, 0), n)
        rng = np.random.default_rng([int(self.ctx.seed), 0x3a])
        temperature = float(self.ctx.traffic.get("temperature", 0.0))
        for i, n in enumerate(sorted(longest.values())):
            prompt = rng.integers(0, vocab, n, dtype=np.int32)
            handle = self.engine.submit(prompt, 2,
                                        temperature=temperature if i == 0
                                        else 0.0)
            list(handle)

    # -------------------------------------------------------------- window --
    def _submit(self, prompt, max_new, temperature):
        return self.engine.submit(prompt, max_new, temperature=temperature)

    def run_window(self, profiler=None):
        """Lead-in, then the window; returns ``(start, end)`` once every
        request sent has finished or the traffic's ``drain_s`` (default 60)
        has passed since the window closed."""
        ctx = self.ctx
        lead = float(ctx.traffic.get("lead_in_s", 0.0))
        self.gen = loadgen.LoadGenerator(ctx.traffic, self.planned,
                                         self._submit)
        t0 = self.gen.start()
        start, end = t0 + lead, t0 + lead + ctx.seconds
        ctx.window = (start, end)
        ctx.phases.append(("lead_in", start))
        if profiler is not None:
            a, b = profiler.plan(start, end)
            sleep_until(a)
            profiler.start()
            sleep_until(b)
            ctx.traced = profiler.interval_so_far()
            ctx.spans = self._program_spans(*ctx.traced)
            profiler.stop()
        sleep_until(end)
        self.gen.stop_sending()
        ctx.counters.update({k: int(v) for k, v in self.engine.stats.items()
                             if isinstance(v, (int, np.integer))})
        self.unfinished = self.gen.drain(
            float(ctx.traffic.get("drain_s", DRAIN_S)))
        ctx.records = self.gen.records
        late = window.percentile(
            window.send_lateness(ctx.records, ctx.window), 95)
        ctx.notes["sent_late_p95_ms"] = None if late is None else 1e3 * late
        return ctx.window

    def _program_spans(self, a, b):
        """The program's own host spans that ended inside ``[a, b]``
        (perf_counter seconds)."""
        from bigdl_tpu import obs
        tracer = obs.default_tracer()
        out = []
        for s in tracer.spans():
            end = tracer.epoch_perf + s.end
            if a <= end <= b:
                out.append((s.name, tracer.epoch_perf + s.start, end,
                            dict(s.attrs or {})))
        return out

    def release(self):
        """Stop the engine and free its device state, so the reference
        runs in an empty chip and after the peak was read."""
        if self.engine is not None:
            self.engine.shutdown(drain=False, timeout=30.0)
        self.engine = None
        self.params = None
        self.model = None

    # ------------------------------------------------------------- correct --
    def attempted_failed(self):
        recs = window.due_in(self.ctx.records, self.ctx.window)
        late = {id(r) for r in self.unfinished}
        failed = sum(1 for r in recs
                     if r.error is not None or id(r) in late
                     or len(r.tokens) != r.planned.max_new)
        return len(recs), failed

    def sample_for_check(self):
        """``(greedy, sampled)``: requests of the window that finished,
        chosen by :func:`choose_for_check`."""
        ctx = self.ctx
        done = [r for r in window.due_in(ctx.records, ctx.window)
                if r.error is None and len(r.tokens) == r.planned.max_new]
        return choose_for_check(done, ctx.seed, ctx.config["check"])

    def verify(self, with_control=False):
        """``(attempted, failed, checks, controls)``. ``checks`` is the
        list of numbers compared, ``{"name", "value", "limit"}``; a value
        above its limit makes the run not correct. ``controls`` maps each
        control's name to the same numbers as the control reads them, put
        in the program's place (``with_control`` only)."""
        import jax
        ctx, cfg = self.ctx, self.ctx.config
        ref_mod = importlib.import_module(
            "benchmarks.reference." + cfg["reference"])
        reference, controls = ref_mod.make(cfg)
        if not with_control:
            controls = {}
        params = weights.make_params(self.shapes, ctx.seed, cfg["weights"],
                                     dtype=cfg["dtype"])
        jax.block_until_ready(params)
        ctx.mark("verify_weights")
        attempted, failed = self.attempted_failed()
        greedy, sampled = self.sample_for_check()
        lim = cfg["check"]
        pmax = int(cfg["constructor_kwargs"]["max_position"])
        # one shape of rows whatever the seed: the longest answer the plan
        # holds; a control reads every position of the prompt as well
        n_rows = pmax if with_control else \
            1 + max(p.max_new for p in self.planned)
        draw = _draw_set(cfg["engine_kwargs"], float(lim.get("top_p_slack", 0)))
        gaps = {"program": []}
        below_set = {"program": []}
        for name in controls:
            gaps[name], below_set[name] = [], []
        for r in greedy + sampled:
            prompt, toks = r.planned.prompt, np.asarray(r.tokens, np.int32)
            n0, m = len(prompt), len(toks)
            seq = np.zeros(pmax, np.int32)
            seq[:n0] = prompt
            seq[n0:n0 + m] = toks
            # row j holds the logits that the token at ``first + j + 1`` is
            # chosen from; everything stays on the device but one number a row
            first = 0 if with_control else n0 - 1
            rows = np.minimum(first + np.arange(n_rows), pmax - 1).astype(
                np.int32)
            nxt = seq[np.minimum(rows + 1, pmax - 1)]
            logits = reference(params, seq, rows)
            served = slice(n0 - 1 - first, n0 - 1 - first + m)
            seen = slice(0, n0 - 1 - first + m)       # rows with a real token
            temperature = float(r.planned.temperature)
            if temperature == 0.0:
                best = logits.max(-1)
                below = np.asarray(best - _pick(logits, nxt))
                gaps["program"].append(below[served])
            else:
                floor, _ = draw(logits, temperature, slack=True)
                below = np.asarray(floor - _pick(logits, nxt))
                below_set["program"].append(np.maximum(below[served], 0.0))
            for name, control in controls.items():
                # the control need not decode: at each position of the same
                # prompt and tokens, the gap of the token IT puts first (in
                # a sampled request: last into the set it draws from)
                theirs = control(params, seq, rows)
                if temperature == 0.0:
                    below = np.asarray(best - _pick(logits, theirs.argmax(-1)))
                    gaps[name].append(below[seen])
                else:
                    below = np.asarray(floor - _pick(
                        logits, draw(theirs, temperature, slack=False)[1]))
                    below_set[name].append(np.maximum(below[seen], 0.0))
        ctx.mark("verify_reference")
        del params
        jax.clear_caches()
        n_tok = sum(len(g) for g in gaps["program"])
        n_sampled = sum(len(g) for g in below_set["program"])
        checks = [
            {"name": "failed_requests", "value": failed, "limit": 0},
            {"name": "checked_tokens_short_of", "value":
                max(0, int(lim["min_tokens"]) - n_tok), "limit": 0}]
        if any(p.temperature > 0.0 for p in self.planned):
            checks.append({"name": "checked_sampled_requests_short_of",
                           "value": max(0, int(lim.get("sampled_requests", 0))
                                        - len(sampled)), "limit": 0})
        checks += _numbers(gaps["program"], below_set["program"], lim)
        ctx.notes["checked"] = {
            "greedy_requests": len(greedy), "greedy_tokens": n_tok,
            "sampled_requests": len(sampled), "sampled_tokens": n_sampled,
            "prefill_classes": sorted({_prefill_class(len(r.planned.prompt))
                                       for r in greedy + sampled})}
        return attempted, failed, checks, {
            name: _numbers(gaps[name], below_set[name], lim)
            for name in controls}


def choose_for_check(done, seed, check):
    """``(greedy, sampled)`` of the finished records ``done``. Greedy: the
    longest, then one of every prefill class they hold (a power-of-two
    prompt length, as the warm-up classes them), then others drawn from the
    seed until ``check["min_tokens"]`` served tokens are in the sample (at
    most ``check["max_requests"]`` requests, and never fewer than those
    chosen first). Sampled: ``check["sampled_requests"]`` of those decoded
    at a temperature, drawn from the seed."""
    rng = np.random.default_rng([int(seed), 0xc4ec])
    warm = [r for r in done if r.planned.temperature > 0.0]
    sampled = [warm[i] for i in rng.permutation(len(warm))[
        :int(check.get("sampled_requests", 0))]]
    cold = [r for r in done if r.planned.temperature == 0.0]
    if not cold:
        return [], sampled
    cold = [cold[i] for i in rng.permutation(len(cold))]
    first = [max(cold, key=lambda r: len(r.planned.prompt) + len(r.tokens))]
    classes = {_prefill_class(len(first[0].planned.prompt))}
    for r in cold:
        if _prefill_class(len(r.planned.prompt)) not in classes:
            classes.add(_prefill_class(len(r.planned.prompt)))
            first.append(r)
    out, n_tok = [], 0
    for r in first + [r for r in cold if all(r is not f for f in first)]:
        if len(out) >= len(first) and (
                n_tok >= int(check["min_tokens"])
                or len(out) >= int(check["max_requests"])):
            break
        out.append(r)
        n_tok += len(r.tokens)
    return out, sampled


def _prefill_class(n):
    """The power-of-two class of a prompt of ``n`` tokens (at least 16)."""
    return max(16, 1 << (n - 1).bit_length())


def _numbers(gaps, below_set, lim):
    """The numbers compared, from every checked token's gap: how far its
    reference logit lies below the reference's best (greedy: the widest
    gap, and the mean of the squared gaps, which grows with the cube of
    the rounding and so tells one precision from the next) or below the
    floor of the set the reference would draw from (sampled)."""
    out = []
    if gaps:
        every = np.concatenate(gaps)
        out += [{"name": "served_logit_gap", "value": float(every.max()),
                 "limit": float(lim["served_logit_gap"])},
                {"name": "served_logit_gap_sq_mean",
                 "value": float(np.square(every, dtype=np.float64).mean()),
                 "limit": float(lim["served_logit_gap_sq_mean"])}]
    if below_set:
        out.append({"name": "sampled_logit_gap",
                    "value": float(np.concatenate(below_set).max()),
                    "limit": float(lim["sampled_logit_gap"])})
    return out


def _draw_set(engine_kwargs, top_p_slack):
    """``draw(logits, temperature, slack) -> (floor, last)``, per row: the
    lowest logit of the set that sampling at ``temperature`` under the
    engine's ``top_k`` and ``top_p`` draws from (temperature, then the
    ``top_k`` largest, then the smallest prefix of them whose mass reaches
    ``top_p``), and the token that holds it. With ``slack`` the mass is
    ``top_p + top_p_slack``: the set a token may lie in, since a rounding
    of the mass moves its edge by a whole token."""
    import jax
    import jax.numpy as jnp
    top_k, top_p = engine_kwargs.get("top_k"), engine_kwargs.get("top_p")

    @jax.jit
    def cut(logits, temperature, mass):
        k = logits.shape[-1] if not top_k else min(int(top_k), logits.shape[-1])
        vals, idx = jax.lax.top_k(logits / temperature, k)
        probs = jax.nn.softmax(vals, axis=-1)
        ahead = jnp.cumsum(probs, axis=-1) - probs
        keep = jnp.sum((ahead < mass).astype(jnp.int32), -1, keepdims=True)
        floor = jnp.take_along_axis(vals, keep - 1, axis=-1)[:, 0]
        last = jnp.take_along_axis(idx, keep - 1, axis=-1)[:, 0]
        return floor * temperature, last

    def draw(logits, temperature, slack):
        mass = 2.0 if top_p is None else float(top_p) + (
            top_p_slack if slack else 0.0)
        return cut(logits, jnp.float32(temperature), jnp.float32(mass))

    return draw


def _pick(logits, tokens):
    """``logits[i, tokens[i]]`` for the rows that ``tokens`` covers."""
    import jax.numpy as jnp
    n = len(tokens)
    return jnp.take_along_axis(logits[:n], jnp.asarray(tokens)[:, None],
                               axis=1)[:, 0]
