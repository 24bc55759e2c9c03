"""Fixed-capacity slot manager: ONE preallocated cache, many requests.

Iteration-level serving (Orca, OSDI '22) needs the decode batch to change
membership every token without changing any array shape: requests arrive
and retire at different times, but XLA wants a single executable. The
slot table delivers that on the PR 3 KV-cache primitives:

- the cache is whatever the model's ``init_cache`` makes (the model
  protocol, ``serving/protocol.py``): one dict a layer, every leaf with
  the slot axis first (S = ``max_slots``), allocated ONCE at
  construction; a request borrows one slot row of every leaf for its
  lifetime. The table looks for no leaf by name: the model's
  ``cache_tables()`` says which leaves a stream fills row by row, how
  many rows a slot has, which row a step writes and how many it reads
  (K and V of every position; or a window that starts over beside chunk
  summaries that gain a row every 16th step); every other leaf is
  fixed-size state such as a convolution's last taps;
- :meth:`admit` prefills up to ``window`` waiting prompts in ONE batched
  causal forward and scatters their rows of every cache leaf + their
  next-token logits into the table (padding rows of a short admission
  batch scatter to index ``max_slots``, which JAX drops as
  out-of-bounds);
- :meth:`step` advances ALL slots by ``steps_per_sync`` tokens in a
  single dispatch: per-slot lengths drive per-row cache writes and
  length-masked attention (``parallel.sequence.cached_attention`` with a
  vector ``cur_len``), greedy/sampled selection is a per-slot
  ``jnp.where`` on the temperature, and inactive rows compute masked
  junk the host ignores;
- :meth:`retire` frees the slot row — no device work: the next admission
  overwrites the WHOLE row of every leaf, so a step's read lengths never
  have to hide a former occupant's rows, however many tables there are.

No shape ever depends on which slots are live, so the step function
compiles exactly once and the engine dispatches O(1) per token
regardless of arrival order. Compile/dispatch telemetry rides in a
``utils.profiling.DecodeCounters`` (same machinery as
``GPTForCausalLM.decode_stats``) and is gated by ``tests/test_serving.py``.
"""

from __future__ import annotations

import heapq

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bigdl_tpu import obs
from bigdl_tpu.models.gpt import prompt_bucket, sample_logits
from bigdl_tpu.nn.moe import grouped_product
from bigdl_tpu.ops import decode_attention, sampling
from bigdl_tpu.ops.kv_write import in_place_applies
from bigdl_tpu.resilience.faults import fault_point
from bigdl_tpu.utils.profiling import DecodeCounters


def select_tokens(logits, temps, key, top_k, top_p, sampler="sort"):
    """Per-slot greedy/sampled token selection shared by the dense and
    paged step traces: greedy argmax everywhere, with the PRNG + softmax
    sampling path behind a runtime ``lax.cond`` so an all-greedy batch
    skips it entirely. ``sampler`` is the manager's word for how the
    sampled branch finds its top-k and nucleus cuts: ``"sort"`` is
    ``sample_logits``, ``"kernel"`` is ``ops.sampling`` (no sort; the
    same key, the same kept set, the same draw; blocks of rows without a
    sampled stream skipped). Returns ``(tok int32 (S,), key)``."""
    greedy_tok = jnp.argmax(logits, axis=-1)
    draws = temps > 0.0
    scale = jnp.maximum(temps, 1e-6)[:, None]

    def pick_sampled(key):
        key, sub = jax.random.split(key)
        if sampler == "kernel":
            sampled = sampling.threshold_sample_logits(
                logits, sub, scale, top_k, top_p, rows=draws)
        else:
            sampled = sample_logits(logits, sub, scale, top_k, top_p)
        return jnp.where(draws, sampled, greedy_tok), key

    tok, key = lax.cond(jnp.any(draws), pick_sampled,
                        lambda key: (greedy_tok, key), key)
    return tok.astype(jnp.int32), key


class DecodeBlock:
    """One decode block between its dispatch and its readback:
    ``toks`` what the step executable returned, still on the device;
    ``dispatched`` what describes the block as it was dispatched (the
    ``serve/step`` attributes known from the host's own table);
    ``read`` what comes back with its tokens (``experts_hit``,
    ``assignments_held``), filled by :meth:`SlotManager.read_step`."""

    __slots__ = ("toks", "dispatched", "read")

    def __init__(self, toks, dispatched):
        self.toks = toks
        self.dispatched = dispatched
        self.read = {}


class SlotManager:
    """Slot-table over one preallocated cache (see module docstring).

    ``model`` speaks the serving protocol (``serving/protocol.py``:
    ``init_cache``/``prefill``/``decode_step``/``logits`` and what it
    tells of itself); speculation, a layout and an adapter pool are for a
    model whose ``serving_features`` carry them (the engine checks).
    ``params`` its live parameters. ``window`` is the prefill-batching
    width (admissions per dispatch), ``steps_per_sync`` the number of
    decode steps fused into one dispatch between host syncs (tokens past
    a request's EOS/max inside a block are discarded by the caller).
    ``top_k``/``top_p`` are engine-wide compile-time sampling config.

    ``layout`` (a ``parallel.layout.ModelLayout``, or None) makes the
    manager sharding-agnostic: with a layout bound, the cache is created
    head-sharded over the mesh's tp axis, the jitted pair carries
    ``out_shardings`` so XLA keeps donated buffers in place (and inserts
    the tensor-parallel collectives — no manual allreduce here), and the
    logits table / PRNG key stay replicated. ``layout=None`` is the
    single-device path, bit-identical to a build without the layout.

    Thread model: NOT thread-safe — exactly one thread (the scheduler
    loop) may call ``admit``/``dispatch_step``/``read_step``/``retire``.
    """

    # the scheduler branches on this: the paged manager
    # (serving/paging.py) admits per-request and prefills in chunks
    paged = False
    # how the decode step writes a new position into the cache, fixed
    # when the pair is built and stamped on every ``serve/step`` span:
    # "kernel" is ``ops/kv_write.py`` in place, "scatter" the plain XLA
    # write (the paged and the speculative steps scatter too)
    kv_write = "scatter"
    # how the decode step's attention reads the cache, fixed with it and
    # stamped beside it: "kernel" is ``ops/decode_attention.py``, each
    # live slot's own blocks of 128 positions and nothing of a free slot;
    # "masked" scores the whole table and masks (the paged and the
    # speculative steps too); "model" is a kernel of the model's own for
    # a table it describes so (``RowTable.own_read``)
    attn_read = "masked"
    # how the sampled branch of token selection finds its top-k and
    # nucleus cuts, fixed with them from the logits table as allocated
    # and stamped beside them: "kernel" is ``ops/sampling.py``, per-row
    # thresholds by bisection; "sort" is ``sample_logits``, two sorts of
    # the table (every step of every manager follows it)
    sampler = "sort"
    # what a model with routed experts adds to the latest
    # ``serve/prefill`` and ``serve/step`` span (docs/observability.md):
    # ``experts`` is the grouped product of the step's (a prefill's is
    # its own, by its shape); None for a model without
    experts = None
    # what the model's own ``step_counts`` / ``prefill_counts`` add to
    # them (host arithmetic on positions: a model whose step reads
    # several row tables says how many rows of each); empty without.
    # ``step_attrs`` is the latest block's that :meth:`step` read back
    prefill_attrs = {}
    step_attrs = {}
    _stat_keys = ("prefill_traces", "step_traces")
    _obs_name = "serving"

    def __init__(self, model, params, max_slots, window=4,
                 steps_per_sync=1, top_k=None, top_p=None, seed=0,
                 spec_tokens=1, layout=None, adapter_pool=None):
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        self.model = model
        self.params = params
        self.layout = layout
        # multi-tenant LoRA (serving/adapters.py): with a pool bound,
        # every prefill/step takes the batch's pre-gathered per-row
        # slab tree as a TRACED argument (never closed over — a
        # cold-adapter load swaps pool buffers without retracing, and
        # the gather itself runs once per admission, not per token)
        # and wraps the params so each batch row decodes against its
        # own adapter. adapter_pool=None is byte-identical to a build
        # without it.
        self.adapter_pool = adapter_pool
        self.tp = 1 if layout is None else layout.tp
        self.mesh_devices = 1 if layout is None else layout.num_devices
        self.max_slots = int(max_slots)
        self.window = max(1, min(int(window), self.max_slots))
        self.steps_per_sync = max(1, int(steps_per_sync))
        # speculative decoding (models/spec.py): gamma > 1 switches the
        # step executable to draft/verify/commit iterations that commit
        # 1..gamma tokens per slot each — the host reads per-slot commit
        # counts alongside the token block (``last_counts``)
        self.spec_tokens = max(1, int(spec_tokens))
        # positions one decode block may write (reserve_block sizes the
        # paged reservation by it): every spec iteration can commit up
        # to gamma tokens, and its rejected overshoot must still land in
        # slot-owned storage
        self.block_span = self.steps_per_sync * self.spec_tokens
        if self.spec_tokens > 1:
            from bigdl_tpu.models.spec import NGramDraft
            self._draft = NGramDraft(model.vocab_size)
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_rollbacks = 0
        self.last_counts = None
        self.top_k = top_k
        self.top_p = top_p
        self.max_position = model.max_position
        self.stats = DecodeCounters(*self._stat_keys,
                                    obs_name=self._obs_name)
        # the owner's counts of its dispatch-ahead (``Scheduler._serve``):
        # blocks dispatched with another still in flight, and slot-blocks
        # computed for a stream the host then found finished
        self.stats["steps_ahead"] = 0
        self.stats["junk_slot_blocks"] = 0
        # the leaves a stream fills row by row, as the model describes
        # them: which kernels apply and ``attn_blocks`` come from these
        self._tables = tuple(model.cache_tables())
        self._own_reads = (None,) * len(self._tables)
        # a model that counts its own rows on the host: the running sums
        # of what it stamps on the spans, under the same names
        self._counted = hasattr(model, "step_counts")
        if self._counted:
            no_pos = np.zeros(0, np.int32)
            for name in (*model.step_counts(no_pos),
                         *model.prefill_counts(no_pos)):
                self.stats[name] = 0
        if model.experts_per_token:
            self.experts = grouped_product(model.expert_rows(max_slots, 1))
            # running sums beside the compile gates: assignments made by
            # admitted prompts and live slots (a routed layer), the
            # steps' ``experts_hit``, and the prefills by their product
            self.stats["moe_assignments"] = 0
            self.stats["moe_experts_hit"] = 0.0
            for product in ("gmm", "ragged_dot"):
                self.stats[f"moe_prefills_{product}"] = 0
        self._seed = int(seed)
        self._resets = 0
        # a failed dispatch may have consumed its DONATED operands (the
        # cache/logits/key buffers are invalid either way) — poisoned
        # means nothing but reset() may touch device state again
        self.poisoned = False
        # the owner's loop iteration, stamped on the spans opened here so
        # that they join the scheduler's own (``Scheduler._serve`` sets
        # it); ``last_prefill_shape`` is the (rows, bucket) the latest
        # admission was padded to, for its ``serve/prefill`` span
        self.iter = 0
        self.last_prefill_shape = None
        self._dtype = model.serving_dtype(params)
        self._alloc()
        if sampling.applies(self._logits, layout):
            self.sampler = "kernel"
        self._prefill_fn, self._step_fn = self._build_fns()

    def _cache_sharding(self):
        """The dense cache's fitted ``NamedSharding`` (head axis over
        tp), or None without a layout — also the jitted pair's cache
        ``out_shardings`` prefix."""
        if self.layout is None:
            return None
        # a layout is a model's that carries ``tp``: its one row table's
        # leaves, heads second
        shapes = jax.eval_shape(
            lambda: self.model.init_cache(self.max_slots, self._dtype))
        shape = self._table_leaf(shapes, self._tables[0]).shape
        return self.layout.sharding(self.layout.spec.kv_cache(), shape,
                                    allow_replicate=False)

    @staticmethod
    def _table_leaf(cache, table):
        """One leaf of ``table`` (a ``protocol.RowTable``) in ``cache``
        (or in its shapes): the first layer's that has it."""
        name = table.leaves[0]
        return next(c[name] for c in cache if name in c)

    def _alloc(self):
        model, dtype = self.model, self._dtype
        self._cache = model.init_cache(self.max_slots, dtype,
                                       sharding=self._cache_sharding())
        self._logits = jnp.zeros((self.max_slots, model.vocab_size),
                                 model.logits_dtype or dtype)
        # distinct stream per incarnation so a rebuilt table does not
        # replay the sampled tokens of the one it replaces
        self._key = jax.random.fold_in(jax.random.key(self._seed),
                                       self._resets)
        if self.layout is not None:
            repl = self.layout.replicated
            self._logits = jax.device_put(self._logits, repl)
            self._key = jax.device_put(self._key, repl)
        # host-side slot table (mirrors the device arrays passed per step)
        self.lengths = np.zeros(self.max_slots, np.int32)
        self.active = np.zeros(self.max_slots, bool)
        self.temps = np.zeros(self.max_slots, np.float32)
        self._free = list(range(self.max_slots))   # heap: lowest slot first
        # occupancy mirror of the free list: a plain int the owner
        # thread maintains, readable lock-free from any thread (the
        # heap itself is owner-only)
        self._occupied = 0
        if self.spec_tokens > 1:
            # per-slot draft state, donated through prefill and step
            # like the cache; rebuilt (and re-primed by re-admission)
            # on reset — replicated under a layout (tiny, host-driven)
            self._table = self._draft.init_state(self.max_slots)
            if self.layout is not None:
                self._table = jax.device_put(self._table,
                                             self.layout.replicated)
        # last committed token per slot — the draft's ``observe`` needs
        # the (prev, tok) bigram spanning a block boundary; the host
        # knows it from the delivered tokens, so it rides in as a plain
        # input instead of more donated device state
        self._last_tok = np.zeros(self.max_slots, np.int32)
        # per-slot adapter pool row (0 = base model); host-side like
        # lengths/temps, passed to every dispatch when a pool is bound
        self.adapter_slots = np.zeros(self.max_slots, np.int32)

    def reset(self):
        """Discard ALL slot state and reallocate the device buffers —
        recovery entry point after a failed dispatch (which may have
        consumed the donated cache). The jitted pair is kept: shapes are
        unchanged, so no recompile. The caller re-prefills whatever
        should survive."""
        self._resets += 1
        self._alloc()
        self.poisoned = False

    # ---------------------------------------------------------- adapters --
    def _wrap_fn(self):
        """Trace-time params transform for the jitted pair: with an
        adapter pool bound, wrap the target weights as LoRA leaves
        carrying the dispatch's pre-gathered per-row slabs; without
        one, the identity — the trace (and its executable) is
        byte-identical to a pool-less build."""
        if self.adapter_pool is None:
            return lambda params, adapter: params
        from bigdl_tpu.models.lora import wrap_params_gathered
        return lambda params, adapter: wrap_params_gathered(
            params, adapter[0])

    def _adapter_args(self, rows):
        """The extra dispatch operand when a pool is bound: the per-row
        slab tree, gathered once per batch-composition change and
        memoized (``AdapterPool.gathered``) — the per-token step never
        re-gathers from the full pool."""
        if self.adapter_pool is None:
            return ()
        return (self.adapter_pool.gathered(rows),)

    # ------------------------------------------------------- jitted pair --
    def _build_fns(self):
        if self.spec_tokens > 1:
            return self._build_spec_fns()
        model = self.model
        stats = self.stats
        n_steps = self.steps_per_sync
        top_k, top_p, sampler = self.top_k, self.top_p, self.sampler
        pmax = self.max_position
        wrap = self._wrap_fn()
        cache_dtype = self._dtype
        # the row tables as they were allocated say whether the write
        # kernel takes the step's writes (each on a TPU, no mesh, rows
        # minor on the device) ...
        tables = self._tables
        allocated = [self._table_leaf(self._cache, t) for t in tables]
        in_place = bool(tables) and all(
            t.kernel_shaped and in_place_applies(a, self.layout)
            for t, a in zip(tables, allocated))
        self.kv_write = "kernel" if in_place else "scatter"
        # ... and whether the length-bounded attention takes its read:
        # the kernel scores ONE table a slot under one softmax, from row
        # 0 on: never a table whose rows read are chosen on the device
        bounded = len(tables) == 1 and tables[0].kernel_shaped \
            and decode_attention.applies(allocated[0], self.layout)
        # a model may read a table through a kernel of its own: the
        # table's description says whether it takes the table as allocated
        # and what it then fetches (``RowTable.own_read``)
        self._own_reads = [t.own_read and t.own_read(a)
                           for t, a in zip(tables, allocated)]
        self.attn_read = "kernel" if bounded else \
            "model" if any(self._own_reads) else "masked"
        # routed experts: the step also counts the experts its live slots
        # hit, one number a step beside the tokens
        routed = bool(model.experts_per_token)

        def prefill(params, cache, logits_buf, ids, prompt_len, slot_idx,
                    *adapter):
            # ids (W, bucket); prompt_len/slot_idx (W,). Padding rows of a
            # short batch carry slot_idx == max_slots: their scatter
            # updates are out-of-bounds and dropped. ``adapter`` is
            # (pre-gathered per-row slab tree,) when a pool is bound.
            stats.tick("prefill_traces")   # trace-time only: counts compiles
            params = wrap(params, adapter)
            tmp = model.init_cache(ids.shape[0], cache_dtype)
            h_last, tmp = model.prefill(params, tmp, ids, prompt_len)
            rows = model.logits(params, h_last)              # (W, vocab)
            # every leaf has the slot axis first, whatever it holds
            cache = jax.tree_util.tree_map(
                lambda c, t: c.at[slot_idx].set(t), cache, tmp)
            logits_buf = logits_buf.at[slot_idx].set(
                rows.astype(logits_buf.dtype))
            return cache, logits_buf

        def step(params, cache, logits_buf, lengths, active, temps, key,
                 *adapter):
            stats.tick("step_traces")      # trace-time only: counts compiles
            params = wrap(params, adapter)

            def one(carry, _):
                cache, logits, lengths, key = carry
                # both selection branches live in the ONE step trace (no
                # recompile); at runtime an all-greedy batch skips the
                # PRNG + softmax sampling work entirely — a measurable
                # per-step cost at small model sizes
                tok, key = select_tokens(logits, temps, key, top_k, top_p,
                                         sampler)
                # clamp: a slot that hit EOS/max mid-block keeps decoding
                # junk the host discards; the clamp keeps its cache writes
                # and position lookups in bounds near max_position
                pos = jnp.minimum(lengths, pmax - 1)
                # a live slot's attention reads the rows its table says
                # (K/V: up to the position it writes), a free slot's none
                read = jnp.where(active, tables[0].read_rows(pos), 0) \
                    if bounded else None
                # ... and the write kernel moves a live slot's blocks only
                h, cache, *hit = model.decode_step(
                    params, cache, tok, pos, in_place=in_place, live=active,
                    read=read)
                if routed:
                    tok = (tok, *hit)
                logits = model.logits(params, h).astype(logits.dtype)
                lengths = lengths + active.astype(lengths.dtype)
                return (cache, logits, lengths, key), tok

            lengths = jnp.asarray(lengths, jnp.int32)
            (cache, logits_buf, _, key), toks = lax.scan(
                one, (cache, logits_buf, lengths, key), None,
                length=n_steps)
            # toks (n_steps, S); routed: (toks, hits (n_steps,)[, held])
            return cache, logits_buf, key, toks

        # ``jax.jit`` names an executable ``jit_`` + its function's name,
        # and the benchmark finds this pair in the device trace as
        # ``jit_prefill`` and ``jit_step`` (``executables`` in
        # benchmarks/configs/gpt2-medium-serve.json): named here on
        # purpose, whatever the local functions come to be called
        # (tests/test_executable_names.py holds it)
        prefill.__name__, step.__name__ = "prefill", "step"
        # the cache, logits table and PRNG key are single-owner buffers
        # threaded call-to-call — donate them; params never are. Under a
        # layout the out_shardings pin every donated output to its input
        # placement (cache head-sharded, the rest replicated) so the
        # buffers never migrate between blocks.
        if self.layout is None:
            return (jax.jit(prefill, donate_argnums=(1, 2)),
                    jax.jit(step, donate_argnums=(1, 2, 6)))
        ckv, repl = self._cache_sharding(), self.layout.replicated
        return (jax.jit(prefill, donate_argnums=(1, 2),
                        out_shardings=(ckv, repl)),
                jax.jit(step, donate_argnums=(1, 2, 6),
                        out_shardings=(ckv, repl, repl, repl)))

    def _build_spec_fns(self):
        """Speculative (prefill, step) pair, for a model that carries
        ``spec_tokens`` (GPT-2: it reaches ``.gpt``'s ``decode_chunk``,
        which the protocol does not name) — same host contract shapes
        as the sequential pair except the step's token block is
        ``(steps_per_sync * gamma, max_slots)`` with per-slot commit
        counts: each of ``steps_per_sync`` scan iterations proposes
        ``gamma`` draft tokens per slot, verifies them in ONE
        ``decode_chunk`` forward, and commits the accepted prefix
        (greedy rows 1..gamma, temperature > 0 rows exactly their one
        sampled token, inactive rows nothing). Rejected tokens need no
        undo: their K/V sit past the committed length, masked off and
        overwritten by the next iteration's chunk. Still one compile
        per executable and ONE dispatch per block."""
        from bigdl_tpu.models.spec import accept_serving
        model, gpt = self.model, self.model.gpt
        stats = self.stats
        n_steps = self.steps_per_sync
        gamma = self.spec_tokens
        top_k, top_p, sampler = self.top_k, self.top_p, self.sampler
        draft = self._draft
        s_all = self.max_slots
        width = n_steps * gamma
        wrap = self._wrap_fn()

        def prefill(params, cache, logits_buf, table, ids, prompt_len,
                    slot_idx, *adapter):
            stats.tick("prefill_traces")   # trace-time only: counts compiles
            params = wrap(params, adapter)
            tmp = gpt.init_cache(ids.shape[0], cache[0]["k"].dtype)
            h_last, tmp = gpt.prefill(params["gpt"], tmp, ids, prompt_len)
            rows = model._lm_logits(params, h_last)
            cache = [{"k": c["k"].at[slot_idx].set(t["k"]),
                      "v": c["v"].at[slot_idx].set(t["v"])}
                     for c, t in zip(cache, tmp)]
            logits_buf = logits_buf.at[slot_idx].set(
                rows.astype(logits_buf.dtype))
            # recycle the slot's draft rows: drop the previous stream's
            # bigrams, then learn the admitted prompt's (padding rows
            # carry the dropped out-of-bounds slot index)
            si = jnp.asarray(slot_idx, jnp.int32)
            table = table.at[si].set(0, mode="drop")
            table = draft.prime(table, ids, prompt_len, rows=si)
            return cache, logits_buf, table

        def step(params, cache, logits_buf, lengths, active, temps, key,
                 table, last, *adapter):
            stats.tick("step_traces")      # trace-time only: counts compiles
            params = wrap(params, adapter)
            lengths = jnp.asarray(lengths, jnp.int32)
            live = jnp.asarray(active)
            sampled = jnp.asarray(temps) > 0.0
            # accept-rate telemetry covers only rows actually
            # speculating — sampled rows commit 1/iteration by design
            # and would read as rejections
            spec_rows = live & ~sampled
            n_spec = jnp.sum(spec_rows.astype(jnp.int32))
            g_iota = jnp.arange(gamma, dtype=jnp.int32)[None, :]
            rows = jnp.broadcast_to(
                jnp.arange(s_all, dtype=jnp.int32)[:, None],
                (s_all, gamma))

            def one(carry, _):
                cache, logits, out, counts, key, table, last, tele = carry
                tok0, key = select_tokens(logits, temps, key, top_k, top_p,
                                          sampler)
                props = draft.propose(table, tok0, gamma)      # (S, g)
                h, cache = gpt.decode_chunk(params["gpt"], cache, props,
                                            lengths + counts)
                vl = model._lm_logits(params, h)
                adv, carry_l = accept_serving(props, vl, sampled=sampled,
                                              live=live)
                mask = g_iota < adv[:, None]
                cols = jnp.where(mask, counts[:, None] + g_iota, width)
                out = out.at[rows, cols].set(props, mode="drop")
                prevs = jnp.concatenate([last[:, None], props[:, :-1]],
                                        axis=1)
                # Draft.observe is the n-gram table update (a pure
                # array scatter), not an obs histogram
                # jaxlint: disable-next-line=span-in-jit
                table = draft.observe(table, prevs, props, mask)
                lastc = jnp.take_along_axis(
                    props, (jnp.maximum(adv, 1) - 1)[:, None],
                    axis=1)[:, 0]
                keep = adv > 0
                last = jnp.where(keep, lastc, last)
                logits = jnp.where(keep[:, None],
                                   carry_l.astype(logits.dtype), logits)
                tele = tele + jnp.stack([
                    gamma * n_spec,
                    jnp.sum(jnp.where(spec_rows, adv, 0)),
                    jnp.sum(jnp.where(spec_rows, gamma - adv, 0))])
                return (cache, logits, out, counts + adv, key, table,
                        last, tele), None

            init = (cache, logits_buf, jnp.zeros((s_all, width), jnp.int32),
                    jnp.zeros((s_all,), jnp.int32), key, table,
                    jnp.asarray(last, jnp.int32),
                    jnp.zeros((3,), jnp.int32))
            (cache, logits_buf, out, counts, key, table, _, tele), _ = \
                lax.scan(one, init, None, length=n_steps)
            # (width, S) token block + per-slot commit counts +
            # (proposed, accepted, rejected) telemetry
            return cache, logits_buf, key, table, out.T, counts, tele

        if self.layout is None:
            return (jax.jit(prefill, donate_argnums=(1, 2, 3)),
                    jax.jit(step, donate_argnums=(1, 2, 6, 7)))
        ckv, repl = self._cache_sharding(), self.layout.replicated
        return (jax.jit(prefill, donate_argnums=(1, 2, 3),
                        out_shardings=(ckv, repl, repl)),
                jax.jit(step, donate_argnums=(1, 2, 6, 7),
                        out_shardings=(ckv,) + (repl,) * 6))

    # --------------------------------------------------------- host side --
    def free_slots(self):
        return self.max_slots - self._occupied

    def occupancy(self):
        """Active slot count — reads the owner-maintained counter, not
        the live free-list heap, so ``engine.metrics()`` may call it
        from any thread."""
        return self._occupied

    def admit(self, prompts, temperatures=None, adapter_slots=None):
        """Prefill ``prompts`` (<= window, <= free slots) into free slots
        in ONE dispatch; returns the assigned slot ids in order.

        The admission batch is padded to the full ``window`` width (rows
        scattered to the dropped out-of-bounds slot) and prompts to the
        shared ``prompt_bucket`` of the longest one, so the executable is
        keyed only on the bucket. ``adapter_slots`` (with a pool bound)
        gives each prompt's acquired pool row; padding rows gather the
        zero-delta base row 0."""
        if not prompts:
            return []
        if len(prompts) > min(self.window, len(self._free)):
            raise ValueError(
                f"admit batch of {len(prompts)} exceeds window "
                f"{self.window} / free slots {len(self._free)}")
        with obs.leaf_span("serve/prefill.pack", iter=self.iter):
            w = self.window
            arrs = [np.asarray(p, np.int32).reshape(-1) for p in prompts]
            for a in arrs:
                if a.size > self.max_position - 1:
                    # reject instead of silently clamping (the table cannot
                    # hold the prompt AND a generated token in bounds)
                    raise ValueError(
                        f"prompt of {a.size} tokens exceeds the slot "
                        f"capacity of {self.max_position - 1} "
                        f"(max_position {self.max_position} minus one "
                        f"generated token)")
            bucket = prompt_bucket(max(a.size for a in arrs),
                                   self.max_position)
            ids = np.zeros((w, bucket), np.int32)
            lens = np.ones(w, np.int32)            # padding rows: length 1
            slot_idx = np.full(w, self.max_slots, np.int32)  # OOB -> dropped
            arows = np.zeros(w, np.int32)          # padding rows: base row 0
            assigned = []
            # before any slot is claimed: a fault here must not leak slots
            fault_point("serving.prefill", n=len(arrs))
            for i, a in enumerate(arrs):
                ids[i, :a.size] = a
                lens[i] = a.size
                slot_idx[i] = heapq.heappop(self._free)
                assigned.append(int(slot_idx[i]))
                if adapter_slots is not None:
                    arows[i] = int(adapter_slots[i])
            self._occupied += len(assigned)
            self.last_prefill_shape = (w, bucket)
            extra = self._adapter_args(arows)
        try:
            with obs.leaf_span("serve/prefill.dispatch", iter=self.iter):
                if self.spec_tokens > 1:
                    self._cache, self._logits, self._table = \
                        self._prefill_fn(
                            self.params, self._cache, self._logits,
                            self._table, ids, lens, slot_idx, *extra)
                else:
                    self._cache, self._logits = self._prefill_fn(
                        self.params, self._cache, self._logits, ids, lens,
                        slot_idx, *extra)
        except BaseException:
            self.poisoned = True
            raise
        self.stats.dispatched()
        attrs = {}
        if self.experts is not None:
            asked = self.model.experts_per_token * sum(a.size for a in arrs)
            self.stats.add("moe_assignments", asked)
            product = grouped_product(self.model.expert_rows(w, bucket))
            self.stats.add(f"moe_prefills_{product}", 1)
            attrs.update(experts=product, assignments=asked)
        if self._counted:
            attrs.update(self._summed(
                self.model.prefill_counts(lens[:len(arrs)])))
        self.prefill_attrs = attrs
        for i, s in enumerate(assigned):
            self.lengths[s] = lens[i]
            self.active[s] = True
            self.temps[s] = (0.0 if temperatures is None
                             else float(temperatures[i]))
            self._last_tok[s] = arrs[i][-1]
            self.adapter_slots[s] = arows[i]
        return assigned

    def _summed(self, counts):
        """The model's own counts, added to their running sums."""
        for name, n in counts.items():
            self.stats.add(name, n)
        return counts

    @property
    def runs_ahead(self):
        """Whether the next block's inputs are known before the last
        block's tokens are: the step draws its token on the device from
        the logits table it carries, and the host passes ``lengths``,
        ``active`` and ``temps``, which advance by arithmetic at
        dispatch. Then the owner may dispatch block N+1 before it reads
        block N back (``Scheduler._serve``). Not under speculation: the
        commit counts and the last committed token come back with the
        block."""
        return self.spec_tokens == 1

    def dispatch_step(self):
        """Dispatch one block of ``steps_per_sync`` decode steps across
        every slot and return it as a :class:`DecodeBlock`, unread: the
        call returns when the executable is on the device's queue. The
        host's ``lengths`` advance HERE, so the next block can be
        dispatched before this one is read (:attr:`runs_ahead`; under
        speculation they advance at the readback, by the counts it
        brings). The arguments are copies: the owner may retire a slot
        while the block is still queued."""
        attrs = {}
        live = self.active.copy()
        if self._counted:
            # from the positions this block's steps write, before they move
            pos = self.lengths[live][:, None] \
                + np.arange(self.steps_per_sync)
            attrs = self._summed(self.model.step_counts(pos.ravel()))
        if self.experts is not None:
            asked = int(live.sum()) * self.model.experts_per_token
            self.stats.add("moe_assignments", self.steps_per_sync * asked)
            attrs.update(experts=self.experts, assignments=asked)
        try:
            # argument hand-over and the call, until the executable's call
            # returns
            with obs.leaf_span("serve/step.dispatch", iter=self.iter):
                extra = self._adapter_args(self.adapter_slots)
                if self.spec_tokens > 1:
                    (self._cache, self._logits, self._key, self._table,
                     *toks) = self._step_fn(
                        self.params, self._cache, self._logits,
                        self.lengths, self.active, self.temps, self._key,
                        self._table, self._last_tok, *extra)
                else:
                    self._cache, self._logits, self._key, toks = \
                        self._step_fn(
                            self.params, self._cache, self._logits,
                            self.lengths.copy(), live, self.temps.copy(),
                            self._key, *extra)
                    # the transfer queued behind the block: the readback
                    # finds the tokens on the host, or on their way
                    for leaf in jax.tree_util.tree_leaves(toks):
                        leaf.copy_to_host_async()
        except BaseException:
            self.poisoned = True
            raise
        self.stats.dispatched()
        if self.spec_tokens == 1:
            self.lengths[live] = np.minimum(
                self.lengths[live] + self.steps_per_sync, self.max_position)
        return DecodeBlock(toks, attrs)

    def read_step(self, block):
        """The host's half of a block: ONE readback, the host blocked on
        the device until the block's tokens are there. Returns host
        tokens of shape (steps_per_sync, max_slots); rows of slots that
        were inactive at the dispatch are junk the caller must ignore.
        With ``spec_tokens`` > 1 the block is (steps_per_sync *
        spec_tokens, max_slots) and ``last_counts`` holds each slot's
        committed count — callers read column ``s`` up to
        ``last_counts[s]``."""
        if self.spec_tokens > 1:
            return self._finish_spec_block(*block.toks)
        with obs.leaf_span("serve/step.readback", iter=self.iter):
            toks = jax.device_get(block.toks)
        if self.experts is not None:
            toks, hits, *held = toks
            self.stats.add("moe_experts_hit", float(hits.sum()))
            block.read["experts_hit"] = float(hits.mean())
            if held:
                # a holder of a SHARE of the experts: how many of those
                # assignments fell on the experts it holds
                self.stats.setdefault("moe_assignments_held", 0.0)
                self.stats.add("moe_assignments_held", float(held[0].sum()))
                block.read["assignments_held"] = float(held[0].mean())
        return toks

    def step(self):
        """One block dispatched and read back at once (:meth:`read_step`
        has the shapes); ``step_attrs`` then holds what describes it."""
        block = self.dispatch_step()
        toks = self.read_step(block)
        self.step_attrs = {**block.dispatched, **block.read}
        return toks

    def _finish_spec_block(self, toks, counts, tele):
        """Host bookkeeping after a speculative block: one readback for
        tokens + commit counts + accept telemetry, then advance lengths
        by each slot's ACTUAL committed count (speculation makes block
        progress variable, 1..block_span tokens per slot)."""
        with obs.leaf_span("serve/step.readback", iter=self.iter):
            toks, counts, tele = jax.device_get((toks, counts, tele))
        counts = np.asarray(counts, np.int64)
        self.last_counts = counts
        self.lengths[self.active] = np.minimum(
            self.lengths[self.active] + counts[self.active],
            self.max_position)
        # the (prev, tok) bigram for the next block's draft observe
        hit = self.active & (counts > 0)
        if hit.any():
            idx = np.nonzero(hit)[0]
            self._last_tok[idx] = toks[counts[idx] - 1, idx]
        self.spec_proposed += int(tele[0])
        self.spec_accepted += int(tele[1])
        self.spec_rollbacks += int(tele[2])
        return toks

    def kv_write_slots(self):
        """The slots whose blocks the next decode step's write moves a
        layer, from the host's own ``active``: the live ones where the
        kernel takes the write, every slot of the table where the
        scatter does."""
        if self.kv_write == "kernel":
            return int(np.count_nonzero(self.active))
        return self.max_slots

    def attn_blocks(self):
        """``(read, table)``: the blocks of 128 rows that the next decode
        step's attention reads a layer over the model's row tables, and
        those the tables hold; from the host's own ``lengths`` and
        ``active``. The table's kernel reads a live slot's
        ``read_rows``, a model's own kernel what it says it fetches
        (``RowTable.own_read``), and a masked read every row held."""
        def blocks(rows):
            return -(-rows // decode_attention.BLOCK)

        pos = self.lengths[self.active]
        read = table = 0
        for t, own in zip(self._tables, self._own_reads):
            held = self.max_slots * blocks(t.rows)
            table += held
            if own:
                read += int(blocks(own(pos)).sum())
            elif self.attn_read == "kernel":
                read += int(blocks(t.read_rows(pos)).sum())
            else:
                read += held
        return read, table

    def sampled(self):
        """Live slots whose next token is drawn (``temps`` > 0), from the
        host's own table: 0 means the step's sampled branch is not
        taken."""
        return int(np.count_nonzero(self.active & (self.temps > 0.0)))

    def retire(self, slot):
        """Free a slot row (host bookkeeping only — a free slot's stale
        rows are read by nobody, and the next admission overwrites the
        whole row of every leaf)."""
        if not self.active[slot]:
            raise ValueError(f"slot {slot} is not active")
        self.active[slot] = False
        self.lengths[slot] = 0
        self.temps[slot] = 0.0
        self.adapter_slots[slot] = 0
        heapq.heappush(self._free, int(slot))
        self._occupied -= 1
