"""Paged K/V cache: block allocator, page-table decode, prefix sharing.

The dense ``SlotManager`` budgets HBM for the worst case: every slot
owns a full ``max_position`` K/V row whether the request uses 30 tokens
or 3000. PagedAttention (Kwon et al., vLLM, SOSP '23) replaces that with
a single global pool of fixed-size *pages* — ``n_layers`` buffers of
``(num_pages, H, page_size, D)`` — and a per-slot *page table* of int32
pool indices. A request holds only the pages its tokens actually fill,
so the same HBM sustains several times the concurrent streams, and two
requests with the same prompt prefix can point their tables at the SAME
pages (hash-keyed prefix cache, refcounted, copy-on-write on the
partially-filled tail page).

Device-side contract (``parallel/sequence.py`` + ``models/gpt.py``):

- *writes* scatter each new K/V row to ``(page_table[s, pos // ps],
  pos % ps)`` with JAX's out-of-bounds-drop semantics — the page index
  ``num_pages`` is the host-side SENTINEL for "no page", so padding
  rows, masked chunk positions and pageless slots all write nowhere
  without any branch in the trace;
- *reads* gather the whole table row back into a dense
  ``(S, H, max_position, D)`` view (``mode="clip"`` junk beyond a
  stream's length is masked by the exact same length mask the dense
  path uses). ``max_position % page_size == 0`` makes the gathered
  shape IDENTICAL to the dense cache, which is what keeps temperature-0
  decoding token-identical to ``SlotManager``;
- every shape is static: one compile for the chunked-prefill
  executable, one for the decode-step executable, one for the COW page
  copy — and ONE dispatch per decode block across all slots, same
  ``DecodeCounters`` gates as the dense path (plus ``copy_traces``).

Chunked prefill (Sarathi-Serve, OSDI '24): admission only *allocates*
(host work); the prompt is prefilled ``prefill_chunk`` tokens at a time
by :meth:`PagedSlotManager.prefill_tick`, one dispatch advancing up to
``window`` pending prompts, which the scheduler interleaves with decode
blocks — resident streams keep emitting tokens while a 1000-token
prompt trickles in, instead of stalling behind its monolithic prefill.

Admission failure is TYPED: :class:`PagePoolExhausted` (never junk
tokens) — the scheduler reacts by queueing, preempting the newest
stream, or failing the request; ``serving.page_alloc`` is the fault
injection site for forcing it (docs/resilience.md).
"""

from __future__ import annotations

import collections
import hashlib
import heapq
import itertools
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bigdl_tpu.resilience.faults import FaultError, fault_point
from bigdl_tpu.serving.slots import DecodeBlock, SlotManager, select_tokens

logger = logging.getLogger("bigdl_tpu.serving")

# prefix digests are chained per token-aligned block from this seed, so
# a block's digest commits to the ENTIRE prefix before it, not just its
# own tokens — equal digest implies equal (position, token) history and
# therefore bitwise-equal K/V, which is what makes page sharing sound
_CHAIN_SEED = b"bigdl-tpu-prefix-v1"


def chain_seed(adapter_digest=None):
    """Chain seed for prefix digests, domain-separated by adapter
    identity: a K/V page holds activations of (tokens, WEIGHTS), so two
    requests running different LoRA adapters over the same base model
    must never share pages even for identical prompts. Folding the
    16-byte adapter digest into the seed separates every rung of the
    ladder at once — HBM registry, host tier, PageStore — with zero new
    key plumbing. ``None`` (base model) keeps the historical seed, so
    adapter-less serving and old snapshots are untouched."""
    if not adapter_digest:
        return _CHAIN_SEED
    return hashlib.blake2b(_CHAIN_SEED + b"adapter:" + adapter_digest,
                           digest_size=16).digest()


def _block_digest(prev, block):
    return hashlib.blake2b(prev + block.tobytes(), digest_size=16).digest()


def _tail_digest(prev, tail):
    # domain-separated: a partial tail of k tokens must never collide
    # with a full block of the same k tokens
    return hashlib.blake2b(prev + b"tail:" + tail.tobytes(),
                           digest_size=16).digest()


def kv_token_bytes(model, int8=False, dtype=np.float32):
    """K/V bytes ONE cached token costs across every layer (K + V; an
    int8 pool adds one f32 scale per (token, head) for each of K and V
    — ``parallel/sequence.py``'s quantize-on-write layout)."""
    layers = model.gpt.layers
    h = layers[0].attn.n_heads
    d = layers[0].attn.head_dim
    per_head = d * (1 if int8 else np.dtype(dtype).itemsize) \
        + (4 if int8 else 0)
    return 2 * len(layers) * h * per_head


def pages_for_budget(model, page_size, byte_budget, int8=False,
                     dtype=np.float32, tp=1):
    """Page-pool size that fits ``byte_budget`` bytes of K/V — the
    apples-to-apples knob for comparing f32 and int8 pools at equal HBM
    spend: for typical head dims the int8 pool holds nearly 2x the
    pages (ratio ``4D / (D + 4)`` per head against f32).

    ``byte_budget`` is PER-CHIP. With a tensor-parallel mesh active
    (``tp`` > 1) each chip holds only ``1/tp`` of the heads
    (``parallel/layout.py``), so the SAME per-chip budget funds ``tp``
    times the pages — the sharded-serving capacity win."""
    tp = max(1, int(tp))
    per_tok = kv_token_bytes(model, int8, dtype) // tp
    return int(byte_budget) // (per_tok * int(page_size))


class PagePoolExhausted(RuntimeError):
    """No free (or reclaimable) K/V pages for the allocation — a typed
    admission/reservation failure the scheduler turns into queueing,
    preemption, or a clean per-request error. Never junk tokens."""


class PageAllocator:
    """Host-side bookkeeping for the global page pool: free list,
    refcounts, and the hash-keyed prefix cache.

    Pure host data structure — it never touches device memory; the
    ``PagedSlotManager`` owns the actual pool buffers and dispatches.

    A page is in exactly one of three states:

    - *free*: on the ``heapq`` free list (lowest index first, like the
      slot heap), contents meaningless;
    - *live*: ``refcount > 0`` — one or more slots reference it from
      their page tables (shared prefix pages have refcount > 1);
    - *reclaimable*: ``refcount == 0`` but still registered in the
      prefix cache — its K/V is intact and a future admission may
      resurrect it (LRU order); :meth:`alloc` evicts these only after
      the free list runs dry, dropping their cache entries.

    ``demote_hook(page, digests)``, when given, fires on each eviction
    BEFORE the page's registrations drop — the host-tier swap-out path
    (``serving/host_tier.py``); it must never raise into ``alloc``.
    """

    def __init__(self, num_pages, demote_hook=None):
        if num_pages < 1:
            raise ValueError(f"num_pages must be >= 1, got {num_pages}")
        self.num_pages = int(num_pages)
        self.demote_hook = demote_hook
        self._free = list(range(self.num_pages))
        heapq.heapify(self._free)
        self.refcount = np.zeros(self.num_pages, np.int64)
        self._registry = {}                            # digest -> page
        self._page_keys = collections.defaultdict(set)  # page -> digests
        self._reclaimable = collections.OrderedDict()   # page -> None (LRU)
        self.evictions = 0

    # ------------------------------------------------------------ queries --
    def available(self):
        """Pages an :meth:`alloc` could hand out right now (free plus
        cache-only reclaimable)."""
        return len(self._free) + len(self._reclaimable)

    def in_use(self):
        """Pages referenced by at least one live slot."""
        return self.num_pages - self.available()

    def lookup(self, digest):
        """Prefix-cache probe: the page registered under ``digest``, or
        None. Does NOT claim it — call :meth:`incref` to."""
        return self._registry.get(digest)

    # -------------------------------------------------------- allocation --
    def alloc(self, n, **ctx):
        """Claim ``n`` pages (refcount 1 each); raises
        :class:`PagePoolExhausted` when the pool cannot supply them.
        The ``serving.page_alloc`` fault site fires here — an injected
        error presents as forced exhaustion, exercising the exact
        recovery path a genuinely full pool takes."""
        try:
            fault_point("serving.page_alloc", n=n, **ctx)
        except FaultError as e:
            raise PagePoolExhausted(
                f"injected page-pool exhaustion at serving.page_alloc "
                f"({n} page(s) requested)") from e
        if n > self.available():
            raise PagePoolExhausted(
                f"{n} page(s) requested but only {self.available()} of "
                f"{self.num_pages} available "
                f"({len(self._free)} free, "
                f"{len(self._reclaimable)} reclaimable)")
        got = []
        for _ in range(n):
            if self._free:
                page = heapq.heappop(self._free)
            else:
                # free list dry: evict the least-recently-retired cached
                # prefix page and drop its registrations; with a host
                # tier attached its K/V demotes instead of vanishing
                page, _ = self._reclaimable.popitem(last=False)
                if self.demote_hook is not None:
                    digests = set(self._page_keys.get(page, ()))
                    if digests:
                        try:
                            self.demote_hook(int(page), digests)
                        except BaseException:
                            logger.exception(
                                "host-tier demote hook failed for page "
                                "%d (page dropped)", page)
                self.invalidate_page(page)
                self.evictions += 1
            self.refcount[page] = 1
            got.append(int(page))
        return got

    def incref(self, page):
        """Add a reference (prefix sharing); resurrects a reclaimable
        cached page without touching its contents."""
        if self.refcount[page] == 0:
            self._reclaimable.pop(page, None)
        self.refcount[page] += 1

    def decref(self, page):
        """Drop a reference; at zero the page becomes reclaimable (still
        registered in the prefix cache) or free (not registered)."""
        if self.refcount[page] <= 0:
            raise ValueError(f"decref of unreferenced page {page}")
        self.refcount[page] -= 1
        if self.refcount[page] == 0:
            if self._page_keys.get(page):
                self._reclaimable[page] = None   # newest LRU position
            else:
                heapq.heappush(self._free, int(page))

    # ------------------------------------------------------ prefix cache --
    def register(self, digest, page):
        """Publish ``page`` as holding the prefix identified by
        ``digest`` (first writer wins — a concurrent identical prefill
        keeps its private copy, which simply never gets shared)."""
        if digest in self._registry:
            return
        self._registry[digest] = int(page)
        self._page_keys[page].add(digest)

    def invalidate_page(self, page):
        """Drop every cache entry naming ``page`` (eviction/reset)."""
        for digest in self._page_keys.pop(page, set()):
            self._registry.pop(digest, None)

    def registered(self):
        """``[(digest, page)]`` snapshot of the prefix cache — the
        candidate set a snapshot pass persists."""
        return list(self._registry.items())


class PagedSlotManager(SlotManager):
    """Drop-in ``SlotManager`` over the paged pool (see module
    docstring). Same host contract (``lengths``/``active``/``temps``
    slot tables, ``step``/``retire``/``reset``/``poisoned``), plus:

    - :meth:`admit_one` — host-only admission: page allocation + prefix
      match; the prompt joins the *pending* set, no dispatch;
    - :meth:`prefill_tick` — one dispatch advancing up to ``window``
      pending prompts by one ``prefill_chunk``-token chunk each;
    - :meth:`reserve_block` — pre-decode page reservation for the next
      ``steps_per_sync`` positions of every active slot (allocates new
      pages, copy-on-writes shared tail pages);
    - :meth:`pool_stats` — occupancy / fragmentation / prefix-cache
      telemetry for the per-engine obs registry.

    ``admit`` (the dense signature) still works — it drives each
    prompt's chunks to completion before returning, which is exactly
    what the scheduler's recovery re-placement path needs.
    """

    paged = True
    # the pages a block writes are reserved from the lengths its
    # predecessor left (``reserve_block``), a chunked prefill turns
    # ``active`` on between blocks, and an exhausted pool preempts a
    # stream: the owner reads each block back before it dispatches the next
    runs_ahead = False
    _stat_keys = ("prefill_traces", "step_traces", "copy_traces")
    _obs_name = "serving_paged"
    _load_fn = None

    def __init__(self, model, params, max_slots, num_pages=None,
                 page_size=16, window=4, steps_per_sync=1,
                 prefill_chunk=64, prefix_cache=True, top_k=None,
                 top_p=None, seed=0, spec_tokens=1, int8_kv=False,
                 page_store=None, layout=None, host_tier=None,
                 host_demote=None, host_tier_prefetch=0,
                 adapter_pool=None):
        pmax = model.gpt.max_position
        # int8 K/V pools: quantize-on-write / dequantize-in-gather with
        # per-(page, head, offset) f32 scales (parallel/sequence.py) —
        # just over half the bytes per cached token, so an equal HBM
        # budget holds nearly twice the pages (pages_for_budget)
        self.int8_kv = bool(int8_kv)
        self.page_size = int(page_size)
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if pmax % self.page_size:
            # equality of the gathered K/V shape with the dense cache —
            # the temp-0 parity guarantee — needs an integral page count
            raise ValueError(
                f"max_position ({pmax}) must be a multiple of page_size "
                f"({self.page_size})")
        self.pages_per_slot = pmax // self.page_size
        if num_pages is None:
            # dense-equivalent budget by default; callers shrink it to
            # realize the memory win
            num_pages = int(max_slots) * self.pages_per_slot
        self.num_pages = int(num_pages)
        if self.num_pages < self.pages_per_slot:
            raise ValueError(
                f"num_pages ({self.num_pages}) cannot hold even one "
                f"max-length stream ({self.pages_per_slot} pages)")
        self.prefill_chunk = max(1, int(prefill_chunk))
        self.prefix_cache = bool(prefix_cache)
        # crash-consistent recovery (serving/snapshot.py): a PageStore
        # to probe on prefix-cache misses — restored pages enter the
        # pool, get registered, and the normal sharing path takes over
        self.page_store = page_store
        self.restore_active = False
        self.restored_pages = 0
        self.last_admit_shared = 0
        self.last_admit_total = 0
        # tiered K/V (serving/host_tier.py): evicted pages demote into
        # the pinned-host pool and promote back by digest — the middle
        # rung of the HBM -> host RAM -> PageStore lookup ladder.
        # ``host_demote`` is the copier's submit (async readback off the
        # owner thread); without one, demotions copy synchronously.
        self.host_tier = host_tier
        self._host_demote = host_demote
        self.host_tier_prefetch = int(host_tier_prefetch or 0)
        self.host_promoted_pages = 0
        self.swap_stall_s = 0.0
        # BIGDL_TPU_PAGED_KERNEL + head-sharded pools: hand every
        # layer's attention the mesh BEFORE super().__init__ jits the
        # (chunk, step) pair, so the pallas kernel traces inside a
        # shard_map over the tp axis (head-local — zero collectives)
        if layout is not None:
            for lyr in model.gpt.layers:
                if getattr(lyr.attn, "use_paged_kernel", False):
                    lyr.attn.paged_kernel_mesh = (layout.mesh,
                                                  layout.spec.tp_axis)
        super().__init__(model, params, max_slots, window=window,
                         steps_per_sync=steps_per_sync, top_k=top_k,
                         top_p=top_p, seed=seed, spec_tokens=spec_tokens,
                         layout=layout, adapter_pool=adapter_pool)

    # ------------------------------------------------------------- state --
    def _pool_plane_sharding(self):
        """Fitted ``NamedSharding`` of one 4-D pool plane (head axis
        over tp), or None without a layout."""
        if self.layout is None:
            return None
        attn = self.model.gpt.layers[0].attn
        shape = (self.num_pages, attn.n_heads, self.page_size,
                 attn.head_dim)
        return self.layout.sharding(self.layout.spec.kv_pool(), shape,
                                    allow_replicate=False)

    def _pool_shardings(self):
        """Per-leaf ``NamedSharding`` tree matching ``self._pools`` —
        the jitted trio's pools ``out_shardings`` (int8 scale planes are
        3-D, so a single prefix sharding cannot cover the tree)."""
        lay = self.layout
        if lay is None:
            return None
        return [{k: lay.sharding(
            lay.spec.kv_pool() if v.ndim == 4 else lay.spec.kv_pool_scale(),
            np.shape(v), allow_replicate=False)
            for k, v in pl.items()} for pl in self._pools]

    def _alloc(self):
        model, dtype = self.model, self._dtype
        pool_dtype = jnp.int8 if self.int8_kv else dtype
        self._pools = model.gpt.init_paged_pool(
            self.num_pages, self.page_size, pool_dtype,
            sharding=self._pool_plane_sharding())
        # dtype-aware byte accounting for pool_stats: K + V across every
        # layer, including the f32 scale planes an int8 pool carries
        page_bytes = sum(int(np.prod(v.shape[1:])) * v.dtype.itemsize
                         for pl in self._pools for v in pl.values())
        self._kv_token_bytes = page_bytes // self.page_size
        # per-chip variant: measured from the actual shards, not derived
        # — a tp mesh splits every plane's head axis, so each chip holds
        # 1/tp of the bytes (pages_for_budget sizes pools against THIS)
        if self.layout is None:
            self._kv_token_bytes_per_chip = self._kv_token_bytes
        else:
            chip = sum(int(v.addressable_shards[0].data.nbytes)
                       for pl in self._pools for v in pl.values())
            self._kv_token_bytes_per_chip = (
                chip // self.num_pages // self.page_size)
        self._logits = jnp.zeros((self.max_slots, model.vocab_size), dtype)
        self._key = jax.random.fold_in(jax.random.key(self._seed),
                                       self._resets)
        if self.layout is not None:
            repl = self.layout.replicated
            self._logits = jax.device_put(self._logits, repl)
            self._key = jax.device_put(self._key, repl)
        self.lengths = np.zeros(self.max_slots, np.int32)
        self.active = np.zeros(self.max_slots, bool)
        self.temps = np.zeros(self.max_slots, np.float32)
        self._free = list(range(self.max_slots))
        self._occupied = 0
        # sentinel-filled: rows of free/pageless slots scatter nowhere
        self.page_table = np.full((self.max_slots, self.pages_per_slot),
                                  self.num_pages, np.int32)
        self.allocator = PageAllocator(
            self.num_pages,
            demote_hook=(self._demote_page if self.host_tier is not None
                         else None))
        self._pending = collections.OrderedDict()   # slot -> prefill state
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_hit_tokens = 0
        self.prefix_miss_tokens = 0
        self.cow_copies = 0
        if self.spec_tokens > 1:
            self._table = self._draft.init_state(self.max_slots)
            if self.layout is not None:
                self._table = jax.device_put(self._table,
                                             self.layout.replicated)
        self._last_tok = np.zeros(self.max_slots, np.int32)
        # per-slot adapter pool row (0 = base) — set at admission,
        # gathered into every chunk/step dispatch as a traced argument
        self.adapter_slots = np.zeros(self.max_slots, np.int32)
        self._pool_snapshot = self._compute_pool_stats()

    # ------------------------------------------------------- jitted trio --
    def _build_fns(self):
        stats = self.stats

        def copy(pools, src, dst):
            # copy-on-write: duplicate one page across every layer pool
            # — every plane, so an int8 pool's scale rows travel with
            # their quantized K/V — before a slot writes into its
            # shared tail page
            stats.tick("copy_traces")
            return [{k: v.at[dst].set(v[src]) for k, v in pl.items()}
                    for pl in pools]

        pool_sh = self._pool_shardings()
        if pool_sh is None:
            self._copy_fn = jax.jit(copy, donate_argnums=(0,))
        else:
            self._copy_fn = jax.jit(copy, donate_argnums=(0,),
                                    out_shardings=pool_sh)
        if self.spec_tokens > 1:
            return self._build_spec_fns()
        model, gpt = self.model, self.model.gpt
        n_steps = self.steps_per_sync
        top_k, top_p, sampler = self.top_k, self.top_p, self.sampler
        pmax = self.max_position
        ps = self.page_size
        wrap = self._wrap_fn()

        def chunk(params, pools, logits_buf, page_table, ids, start,
                  nvalid, write_from, slot_final, *adapter):
            # one chunked-prefill dispatch over up to `window` rows;
            # `slot_final` routes the final chunk's next-token logits
            # into the slot's logits row (non-final rows carry the
            # dropped out-of-bounds index max_slots)
            stats.tick("prefill_traces")
            params = wrap(params, adapter)
            h_last, pools = gpt.paged_prefill_chunk(
                params["gpt"], pools, page_table, ids, start, nvalid,
                write_from, ps)
            rows = model._lm_logits(params, h_last)
            logits_buf = logits_buf.at[slot_final].set(
                rows.astype(logits_buf.dtype))
            return pools, logits_buf

        num_pages = self.num_pages

        def step(params, pools, logits_buf, page_table, lengths, active,
                 temps, key, *adapter):
            stats.tick("step_traces")
            params = wrap(params, adapter)
            # inactive rows must not write through their tables: a
            # mid-prefill (pending) slot already owns pages, and the
            # masked junk step every slot computes would corrupt them —
            # sentinel rows scatter nowhere (dense-path equivalent:
            # junk lands in the slot's own dormant cache row)
            page_table = jnp.where(jnp.asarray(active)[:, None],
                                   page_table, num_pages)

            def one(carry, _):
                pools, logits, lengths, key = carry
                tok, key = select_tokens(logits, temps, key, top_k, top_p,
                                         sampler)
                # same clamp as the dense step: a slot that hit EOS/max
                # mid-block keeps decoding junk the host discards
                pos = jnp.minimum(lengths, pmax - 1)
                h, pools = gpt.paged_decode_step(
                    params["gpt"], pools, page_table, tok, pos, ps)
                logits = model._lm_logits(params, h).astype(logits.dtype)
                lengths = lengths + active.astype(lengths.dtype)
                return (pools, logits, lengths, key), tok

            lengths = jnp.asarray(lengths, jnp.int32)
            (pools, logits_buf, _, key), toks = lax.scan(
                one, (pools, logits_buf, lengths, key), None,
                length=n_steps)
            return pools, logits_buf, key, toks

        if pool_sh is None:
            return (jax.jit(chunk, donate_argnums=(1, 2)),
                    jax.jit(step, donate_argnums=(1, 2, 7)))
        repl = self.layout.replicated
        return (jax.jit(chunk, donate_argnums=(1, 2),
                        out_shardings=(pool_sh, repl)),
                jax.jit(step, donate_argnums=(1, 2, 7),
                        out_shardings=(pool_sh, repl, repl, repl)))

    def _build_spec_fns(self):
        """Paged speculative (chunk, step) pair. The chunk fn
        additionally clears + primes the draft table from each prompt
        chunk; the step fn is the dense spec scan over
        ``paged_verify_chunk`` — every write (committed AND rejected)
        lands inside the ``block_span`` positions ``reserve_block``
        guaranteed are slot-owned (boundary pages copy-on-written, the
        rest freshly allocated or the dropped sentinel), so rollback
        can never touch a shared prefix page."""
        from bigdl_tpu.models.spec import accept_serving
        model, gpt = self.model, self.model.gpt
        stats = self.stats
        n_steps = self.steps_per_sync
        gamma = self.spec_tokens
        top_k, top_p, sampler = self.top_k, self.top_p, self.sampler
        ps = self.page_size
        draft = self._draft
        s_all = self.max_slots
        width = n_steps * gamma
        num_pages = self.num_pages
        wrap = self._wrap_fn()

        def chunk(params, pools, logits_buf, page_table, ids, start,
                  nvalid, write_from, slot_final, table, prime_rows,
                  prime_prev, clear_rows, *adapter):
            stats.tick("prefill_traces")
            params = wrap(params, adapter)
            h_last, pools = gpt.paged_prefill_chunk(
                params["gpt"], pools, page_table, ids, start, nvalid,
                write_from, ps)
            lrows = model._lm_logits(params, h_last)
            logits_buf = logits_buf.at[slot_final].set(
                lrows.astype(logits_buf.dtype))
            # first chunk of a recycled slot drops the previous
            # stream's bigrams (later chunks carry the dropped
            # out-of-bounds row index), then every chunk primes its own
            # tokens with the host-supplied preceding token
            table = table.at[jnp.asarray(clear_rows, jnp.int32)].set(
                0, mode="drop")
            table = draft.prime(table, ids, nvalid, rows=prime_rows,
                                prev=prime_prev)
            return pools, logits_buf, table

        def step(params, pools, logits_buf, page_table, lengths, active,
                 temps, key, table, last, *adapter):
            stats.tick("step_traces")
            params = wrap(params, adapter)
            # same sentinel guard as the sequential paged step: inactive
            # rows (free or mid-prefill slots) must not write through
            # their tables
            page_table = jnp.where(jnp.asarray(active)[:, None],
                                   page_table, num_pages)
            lengths = jnp.asarray(lengths, jnp.int32)
            live = jnp.asarray(active)
            sampled = jnp.asarray(temps) > 0.0
            spec_rows = live & ~sampled
            n_spec = jnp.sum(spec_rows.astype(jnp.int32))
            g_iota = jnp.arange(gamma, dtype=jnp.int32)[None, :]
            rows = jnp.broadcast_to(
                jnp.arange(s_all, dtype=jnp.int32)[:, None],
                (s_all, gamma))

            def one(carry, _):
                pools, logits, out, counts, key, table, last, tele = carry
                tok0, key = select_tokens(logits, temps, key, top_k, top_p,
                                          sampler)
                props = draft.propose(table, tok0, gamma)
                h, pools = gpt.paged_verify_chunk(
                    params["gpt"], pools, page_table, props,
                    lengths + counts, ps)
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
                return (pools, logits, out, counts + adv, key, table,
                        last, tele), None

            init = (pools, logits_buf,
                    jnp.zeros((s_all, width), jnp.int32),
                    jnp.zeros((s_all,), jnp.int32), key, table,
                    jnp.asarray(last, jnp.int32),
                    jnp.zeros((3,), jnp.int32))
            (pools, logits_buf, out, counts, key, table, _, tele), _ = \
                lax.scan(one, init, None, length=n_steps)
            return pools, logits_buf, key, table, out.T, counts, tele

        pool_sh = self._pool_shardings()
        if pool_sh is None:
            return (jax.jit(chunk, donate_argnums=(1, 2, 9)),
                    jax.jit(step, donate_argnums=(1, 2, 7, 8)))
        repl = self.layout.replicated
        return (jax.jit(chunk, donate_argnums=(1, 2, 9),
                        out_shardings=(pool_sh, repl, repl)),
                jax.jit(step, donate_argnums=(1, 2, 7, 8),
                        out_shardings=(pool_sh,) + (repl,) * 6))

    # --------------------------------------------------------- admission --
    def _match_prefix(self, a, seed=None):
        """Longest token-aligned shared prefix of prompt ``a``: walks
        the chained block digests through the cache, then tries the
        partial tail. ``seed`` domain-separates the chain by adapter
        identity (:func:`chain_seed`) — defaults to the base-model
        chain. Returns ``(digests, tail_dig, shared_pages,
        shared_full, tail_shared)`` — ``shared_pages`` in page-table
        order, NOT yet claimed."""
        ps = self.page_size
        n_full = a.size // ps
        digests, prev = [], (seed or _CHAIN_SEED)
        for b in range(n_full):
            prev = _block_digest(prev, a[b * ps:(b + 1) * ps])
            digests.append(prev)
        tail = a[n_full * ps:]
        tail_dig = _tail_digest(prev, tail) if tail.size else None
        if not self.prefix_cache:
            return digests, tail_dig, [], 0, False
        shared_pages, shared_full = [], 0
        # While a store OR host tier is attached, a restore's ``alloc``
        # may EVICT reclaimable pages — including ones already collected
        # here (the tier-less path never allocates mid-match, so
        # admit_one's incref-first claim was enough). Pin each match for
        # the duration of the walk; ``restore_active`` is raised while
        # restore I/O is possible so the supervisor's wedge detector
        # extends its heartbeat grace
        # (docs/resilience.md#crash-consistent-recovery).
        pin = self.page_store is not None or self.host_tier is not None
        try:
            for b in range(n_full):
                page = self.allocator.lookup(digests[b])
                if page is None:
                    break
                if pin:
                    self.allocator.incref(page)
                shared_pages.append(page)
                shared_full = b + 1
            if pin and shared_full < n_full:
                self.restore_active = True
                for page in self._restore_pages(
                        digests[shared_full:n_full]):
                    self.allocator.incref(page)
                    shared_pages.append(page)
                    shared_full += 1
            tail_shared = False
            if tail_dig is not None and shared_full == n_full:
                page = self.allocator.lookup(tail_dig)
                if page is None and pin:
                    self.restore_active = True
                    pages = self._restore_pages([tail_dig])
                    page = pages[0] if pages else None
                if page is not None:
                    if pin:
                        self.allocator.incref(page)
                    shared_pages.append(page)
                    tail_shared = True
        finally:
            if pin:
                for page in shared_pages:
                    self.allocator.decref(page)
            self.restore_active = False
        return digests, tail_dig, shared_pages, shared_full, tail_shared

    def _restore_pages(self, digests):
        """Fetch a consecutive run of demoted/snapshotted pages by
        digest into fresh pool pages with ONE batched load dispatch,
        registering each (reclaimable, exactly like a retired cached
        prefix page — the caller's ``incref`` claims them). Each digest
        walks the ladder's lower rungs (:meth:`_fetch_restore`: host
        tier, then PageStore); the run stops at the first full miss,
        checksum demotion, injected fault, or plane-layout mismatch,
        and trims to the pool's spare capacity — every failure mode
        degrades to a prefix-cache miss and the existing re-prefill
        path. A digest still registered mid-run (the caller's walk
        stops at its FIRST miss, but LRU eviction does not respect
        chain order, so later links may survive in HBM) reuses its
        live page — loading a duplicate would be refused by the
        first-writer-wins registry and the fresh page, freed by the
        decref below while still being handed to the caller, would
        end up owned by two slots. Returns the page indices actually
        restored or reused (a prefix of ``digests``)."""
        plan = []          # (digest, planes | None, from_tier, page | None)
        loads = 0
        # leave one spare page so the restore itself can never strand
        # admission with a pool it just filled
        spare = max(0, self.allocator.available() - 1)
        for digest in digests:
            page = self.allocator.lookup(digest)
            if page is not None:
                plan.append((digest, None, False, page))
                continue
            if loads >= spare:
                break
            planes, from_tier = self._fetch_restore(digest)
            if planes is None or not self._planes_compatible(planes):
                break
            plan.append((digest, planes, from_tier, None))
            loads += 1
        if not plan:
            return []
        reused = [e[3] for e in plan if e[3] is not None]
        for page in reused:
            self.allocator.incref(page)  # pin: the alloc must not evict
        try:
            fresh = []
            if loads:
                try:
                    fresh = self.allocator.alloc(loads, restore=True)
                except PagePoolExhausted:
                    # keep the already-live leading run, drop the loads
                    plan = list(itertools.takewhile(
                        lambda e: e[3] is not None, plan))
                    return [e[3] for e in plan]
                try:
                    self._dispatch_load(
                        fresh,
                        [pl for _, pl, _, pg in plan if pg is None])
                except BaseException:
                    for page in fresh:
                        self.allocator.decref(page)
                    raise
            out, it = [], iter(fresh)
            for digest, _, from_tier, page in plan:
                if page is None:
                    page = next(it)
                    self.allocator.register(digest, page)
                    self.allocator.decref(page)  # cached until claimed
                    if from_tier:
                        self.host_promoted_pages += 1
                    else:
                        self.restored_pages += 1
                out.append(page)
            return out
        finally:
            for page in reused:
                self.allocator.decref(page)

    def _fetch_restore(self, digest):
        """Lower rungs of the digest ladder — the caller already missed
        the HBM registry. Probes the pinned-host tier first (checksum
        re-verified inside :meth:`HostPageTier.get`; a corrupt buffer
        is dropped there and falls through), then the on-disk
        PageStore. Returns ``(planes, from_tier)`` — ``(None, False)``
        on a full miss. The ``serving.host_swap`` fault site fires on
        the tier probe; an injected error presents as a tier miss, so
        the stream degrades to the store rung / re-prefill."""
        if self.host_tier is not None:
            t0 = time.perf_counter()
            try:
                fault_point("serving.host_swap", op="promote")
                planes = self.host_tier.get(digest)
            except FaultError as e:
                logger.warning("injected host-swap promote fault "
                               "(presenting as a tier miss): %r", e)
                planes = None
            self.swap_stall_s += time.perf_counter() - t0
            if planes is not None:
                return planes, True
        if self.page_store is not None:
            planes = self.page_store.get(digest)
            if planes is not None:
                return planes, False
        return None, False

    def _demote_page(self, page, digests):
        """Eviction demote hook (owner thread, fired by
        ``PageAllocator.alloc`` before the page's registrations drop):
        stage the page's K/V into the host tier instead of dropping it.
        Owner-thread cost is the per-plane slice — asynchronous device
        dispatches producing private buffers the next donated dispatch
        cannot touch — plus a queue put; the blocking readback,
        owning copy and checksum run on the copier thread overlapped
        with the next decode block (``DeviceFeed`` pattern). Under a tp
        mesh the slices gather to fully-replicated full-H first, so
        demoted pages stay mesh-portable exactly like ``export_pages``
        output. Must never raise into ``alloc``."""
        tier = self.host_tier
        if tier is None:
            return
        t0 = time.perf_counter()
        eid = None
        try:
            fault_point("serving.host_swap", op="demote", page=int(page))
            eid = tier.stage(digests,
                             self._kv_token_bytes * self.page_size)
            if eid is None:
                return
            planes = [{k: v[page] for k, v in pl.items()}
                      for pl in self._pools]
            if self.layout is not None:
                planes = jax.device_put(planes, self.layout.replicated)
        except FaultError as e:
            logger.warning("injected host-swap demote fault "
                           "(page dropped): %r", e)
            if eid is not None:
                tier.abort(eid)
            return
        except BaseException:
            logger.exception("host-tier demote staging failed "
                             "(page dropped)")
            if eid is not None:
                tier.abort(eid)
            return
        finally:
            self.swap_stall_s += time.perf_counter() - t0
        if self._host_demote is not None:
            self._host_demote(eid, planes)
        else:
            tier.ingest(eid, planes)     # synchronous fallback (no copier)

    def preserve_stream(self, tokens, slot, seed=None):
        """Swap-aware preemption (owner thread, scheduler ``_preempt``):
        register the about-to-be-retired stream's written full-block —
        and exact-tail — digests so retirement leaves its pages
        *reclaimable* instead of free. Pool pressure then demotes them
        through the host tier, and the stream's resume admission
        full-prefix-hits (registry or promote) instead of re-prefilling
        its whole context. Decode-written pages carry exactly the
        tokens the chain digests commit to — the same soundness
        argument as ``_finalize_prefill``'s registrations. Returns the
        number of pages newly registered."""
        if self.host_tier is None or not self.prefix_cache \
                or not self.active[slot]:
            return 0
        a = np.asarray(tokens, np.int32).reshape(-1)
        t = min(a.size, int(self.lengths[slot]))
        row = self.page_table[slot]
        ps, sentinel = self.page_size, self.num_pages
        n_full = t // ps
        count = 0
        prev = seed or _CHAIN_SEED
        for b in range(n_full):
            prev = _block_digest(prev, a[b * ps:(b + 1) * ps])
            page = int(row[b])
            if page != sentinel \
                    and self.allocator.lookup(prev) is None:
                self.allocator.register(prev, page)
                count += 1
        tail = a[n_full * ps:t]
        if tail.size and n_full < self.pages_per_slot:
            page = int(row[n_full])
            tail_dig = _tail_digest(prev, tail)
            if page != sentinel \
                    and self.allocator.lookup(tail_dig) is None:
                self.allocator.register(tail_dig, page)
                count += 1
        return count

    def prefetch_prefix(self, tokens, limit, seed=None):
        """Swap-in prefetch (owner thread): promote up to ``limit`` of
        this prompt's missing full-block pages from the host tier /
        store into the pool BEFORE its admission — the scheduler calls
        this one iteration ahead for the waiting queue's head, so the
        admission-time registry walk hits HBM instead of stalling on
        the swap. Promoted pages are registered reclaimable; LRU order
        keeps them until the admission's incref claims them. Returns
        pages promoted."""
        if self.host_tier is None or not self.prefix_cache \
                or limit <= 0:
            return 0
        a = np.asarray(tokens, np.int32).reshape(-1)
        ps = self.page_size
        n_full = a.size // ps
        digests, prev = [], (seed or _CHAIN_SEED)
        for b in range(n_full):
            prev = _block_digest(prev, a[b * ps:(b + 1) * ps])
            digests.append(prev)
        start = 0
        while start < n_full \
                and self.allocator.lookup(digests[start]) is not None:
            start += 1
        run = digests[start:start + int(limit)]
        if not run:
            return 0
        self.restore_active = True
        try:
            pages = self._restore_pages(run)
        finally:
            self.restore_active = False
            self._refresh_pool_stats()
        return len(pages)

    def _planes_compatible(self, planes):
        """A snapshot written under a different pool layout (page_size,
        dtype, int8 scale planes, layer count) must present as a miss,
        never reach the jitted loader."""
        if len(planes) != len(self._pools):
            return False
        for got, pl in zip(planes, self._pools):
            want = {k: (v.shape[1:], v.dtype) for k, v in pl.items()}
            if set(got) != set(want):
                return False
            for k, a in got.items():
                shape, dtype = want[k]
                if tuple(a.shape) != tuple(shape) \
                        or np.dtype(a.dtype) != np.dtype(dtype):
                    return False
        return True

    def _dispatch_load(self, pages, planes_list):
        """One jitted scatter writing a BATCH of restored pages into the
        pool (donating it, like the COW copy). Batching is what makes
        restore O(restore): a 12-page prompt costs one dispatch, not
        twelve. Specializes per batch size; repeat sizes hit the jit
        cache."""
        stacked = [
            {k: np.stack([pl[li][k] for pl in planes_list])
             for k in planes_list[0][li]}
            for li in range(len(self._pools))]
        if self._load_fn is None:
            stats = self.stats

            def load(pools, dst, planes):
                stats.tick("copy_traces")
                return [{k: v.at[dst].set(planes[i][k])
                         for k, v in pl.items()}
                        for i, pl in enumerate(pools)]

            pool_sh = self._pool_shardings()
            if pool_sh is None:
                self._load_fn = jax.jit(load, donate_argnums=(0,))
            else:
                # host planes are full-H (layout-independent on disk);
                # the scatter lands each chip's head slice in place
                self._load_fn = jax.jit(load, donate_argnums=(0,),
                                        out_shardings=pool_sh)
        try:
            self._pools = self._load_fn(
                self._pools, np.asarray(pages, np.int32), stacked)
        except BaseException:
            self.poisoned = True
            raise
        self.stats.dispatched()

    def export_pages(self, extra=(), skip=None):
        """Owner thread only: owning host copies of every registered
        prefix-cache page plus the ``extra`` ``(digest, page)`` pairs
        (a snapshot pass passes the full-block pages of live streams —
        append-immutable while the slot owns them). ``skip(digest)``
        filters already-persisted pages before any device transfer.
        Returns ``[(digest, planes)]`` where ``planes`` mirrors the
        per-layer pool dicts; every array OWNS its memory
        (``utils.hostcopy``) so a background writer can serialize it
        after the next donated dispatch reuses the pool buffers."""
        from bigdl_tpu.utils.hostcopy import detach
        pairs = []
        for digest, page in self.allocator.registered():
            if skip is not None and skip(digest):
                continue
            pairs.append((digest, int(page)))
        for digest, page in extra:
            if skip is not None and skip(digest):
                continue
            pairs.append((digest, int(page)))
        if not pairs:
            return []
        host = {}
        for _, page in pairs:
            if page not in host:
                host[page] = [{k: v[page] for k, v in pl.items()}
                              for pl in self._pools]
        if self.layout is not None:
            # gather each exported plane to a fully-replicated copy
            # BEFORE the host transfer: the store's on-disk planes are
            # full-H and layout-independent, so pages written by a tp=2
            # engine restore on a tp=1 engine and vice versa
            host = jax.device_put(host, self.layout.replicated)
        host = jax.tree_util.tree_map(detach, jax.device_get(host))
        seen, out = set(), []
        for digest, page in pairs:
            if digest in seen:
                continue
            seen.add(digest)
            out.append((digest, host[page]))
        return out

    def admit_one(self, prompt, temperature=0.0, adapter_slot=0,
                  seed=None):
        """Admit ONE prompt: prefix match + page allocation + slot
        claim — pure host work, no dispatch. The prompt becomes
        *pending*; :meth:`prefill_tick` runs its chunks.
        ``adapter_slot`` is the AdapterPool row this stream decodes
        under (0 = base); ``seed`` is its :func:`chain_seed`, so its
        prefix pages never cross-share with other adapters'. Returns
        the slot id. Raises :class:`PagePoolExhausted` (nothing
        leaked) when the pool cannot hold the unshared part of the
        prompt."""
        a = np.asarray(prompt, np.int32).reshape(-1)
        t = a.size
        if t < 1:
            raise ValueError("empty prompt")
        if t > self.max_position - 1:
            raise ValueError(
                f"prompt of {t} tokens exceeds the slot capacity of "
                f"{self.max_position - 1} (max_position "
                f"{self.max_position} minus one generated token)")
        if not self._free:
            raise ValueError("no free slot")
        ps = self.page_size
        n_full = t // ps
        need_pages = -(-t // ps)               # ceil(t / page_size)
        digests, tail_dig, shared_pages, shared_full, tail_shared = \
            self._match_prefix(a, seed=seed)
        shared_len = t if tail_shared or (shared_full == n_full
                                          and not t % ps) \
            else shared_full * ps
        # claim the matched pages FIRST so alloc's LRU eviction cannot
        # steal them out from under us; roll back if alloc fails
        for page in shared_pages:
            self.allocator.incref(page)
        try:
            new_pages = self.allocator.alloc(
                need_pages - len(shared_pages), prompt_tokens=t)
        except BaseException:
            for page in shared_pages:
                self.allocator.decref(page)
            raise
        slot = heapq.heappop(self._free)
        self._occupied += 1
        row = self.page_table[slot]
        row[:len(shared_pages)] = shared_pages
        row[len(shared_pages):need_pages] = new_pages
        if shared_len == t:
            # full prefix hit: nothing to write — one logits-only chunk
            # replays the last position through the shared pages
            next_pos, write_from = t - 1, t
        else:
            next_pos = write_from = shared_len
        self._pending[slot] = {
            "tokens": a, "total": t, "next": next_pos,
            "write_from": write_from, "temp": float(temperature or 0.0),
            "digests": digests, "tail_dig": tail_dig,
            "shared_full": shared_full, "tail_shared": tail_shared,
        }
        self.adapter_slots[slot] = int(adapter_slot)
        if shared_len:
            self.prefix_hits += 1
        else:
            self.prefix_misses += 1
        self.prefix_hit_tokens += shared_len
        self.prefix_miss_tokens += t - shared_len
        self.last_admit_shared = int(shared_len)
        self.last_admit_total = int(t)
        self._refresh_pool_stats()
        return int(slot)

    def pending_prefills(self):
        """Prompts admitted but not yet fully prefilled."""
        return len(self._pending)

    def prefill_tick(self):
        """Advance up to ``window`` pending prompts by one chunk each in
        ONE dispatch; prompts whose final chunk lands become active
        (their next-token logits are in the table). Returns the number
        of prompts still pending afterwards."""
        if not self._pending:
            return 0
        w, c, p = self.window, self.prefill_chunk, self.pages_per_slot
        rows = list(itertools.islice(self._pending.items(), w))
        fault_point("serving.prefill", n=len(rows))
        ids = np.zeros((w, c), np.int32)
        start = np.zeros(w, np.int32)
        nvalid = np.ones(w, np.int32)
        # padding rows: write_from == max_position suppresses every
        # write; their sentinel page-table rows drop the rest
        write_from = np.full(w, self.max_position, np.int32)
        slot_final = np.full(w, self.max_slots, np.int32)  # OOB -> dropped
        pt = np.full((w, p), self.num_pages, np.int32)
        arows = np.zeros(w, np.int32)   # padding rows: base adapter
        spec = self.spec_tokens > 1
        if spec:
            # draft-table maintenance riding the chunk dispatch: which
            # state rows to prime (padding -> dropped OOB), the token
            # preceding each chunk (vocab_size = none), and which rows
            # are a recycled slot's FIRST chunk (cleared before prime)
            prime_rows = np.full(w, self.max_slots, np.int32)
            prime_prev = np.full(w, self.model.vocab_size, np.int32)
            clear_rows = np.full(w, self.max_slots, np.int32)
        finished = []
        for i, (s, st) in enumerate(rows):
            n = min(c, st["total"] - st["next"])
            ids[i, :n] = st["tokens"][st["next"]:st["next"] + n]
            start[i] = st["next"]
            nvalid[i] = n
            write_from[i] = st["write_from"]
            pt[i] = self.page_table[s]
            arows[i] = self.adapter_slots[s]
            if spec:
                prime_rows[i] = s
                if st["next"] > 0:
                    prime_prev[i] = st["tokens"][st["next"] - 1]
                if not st.get("primed"):
                    clear_rows[i] = s
                    st["primed"] = True
            if st["next"] + n >= st["total"]:
                slot_final[i] = s
                finished.append((s, st))
        extra = self._adapter_args(arows)
        try:
            if spec:
                self._pools, self._logits, self._table = self._prefill_fn(
                    self.params, self._pools, self._logits, pt, ids,
                    start, nvalid, write_from, slot_final, self._table,
                    prime_rows, prime_prev, clear_rows, *extra)
            else:
                self._pools, self._logits = self._prefill_fn(
                    self.params, self._pools, self._logits, pt, ids,
                    start, nvalid, write_from, slot_final, *extra)
        except BaseException:
            self.poisoned = True
            raise
        self.stats.dispatched()
        for i, (s, st) in enumerate(rows):
            st["next"] = min(st["next"] + int(nvalid[i]), st["total"])
        for s, st in finished:
            self._finalize_prefill(s, st)
        self._refresh_pool_stats()
        return len(self._pending)

    def _finalize_prefill(self, slot, st):
        """The prompt's last chunk landed: register its privately
        written pages in the prefix cache and flip the slot active."""
        del self._pending[slot]
        if self.prefix_cache:
            row = self.page_table[slot]
            ps = self.page_size
            n_full = st["total"] // ps
            for b in range(st["shared_full"], n_full):
                self.allocator.register(st["digests"][b], row[b])
            if st["tail_dig"] is not None and not st["tail_shared"]:
                self.allocator.register(st["tail_dig"], row[n_full])
        self.lengths[slot] = st["total"]
        self.active[slot] = True
        self.temps[slot] = st["temp"]
        self._last_tok[slot] = st["tokens"][-1]

    def admit(self, prompts, temperatures=None, adapter_slots=None,
              seeds=None):
        """Dense-signature batch admission: admit each prompt and drive
        its chunks to completion before the next, so identical prefixes
        re-form their sharing (the scheduler's recovery re-placement
        path — the normal serve loop interleaves instead)."""
        if not prompts:
            return []
        if len(prompts) > min(self.window, len(self._free)):
            raise ValueError(
                f"admit batch of {len(prompts)} exceeds window "
                f"{self.window} / free slots {len(self._free)}")
        assigned = []
        for i, prompt in enumerate(prompts):
            temp = 0.0 if temperatures is None else float(temperatures[i])
            arow = 0 if adapter_slots is None else int(adapter_slots[i])
            seed = None if seeds is None else seeds[i]
            assigned.append(self.admit_one(prompt, temp,
                                           adapter_slot=arow, seed=seed))
            while self.prefill_tick():
                pass
        return assigned

    # ----------------------------------------------------------- decode --
    def reserve_block(self):
        """Guarantee pages for the next ``block_span`` positions
        (``steps_per_sync``, times ``spec_tokens`` when speculating —
        rejected draft overshoot must land in slot-owned pages too) of
        every active slot: allocates pages for fresh positions and
        copy-on-writes a shared boundary page before the slot writes
        into it. Raises :class:`PagePoolExhausted` when the pool runs
        out — already-granted pages stay recorded in the page tables,
        so the call is idempotent and safe to retry after the scheduler
        frees pages by preempting a stream."""
        ps, sentinel = self.page_size, self.num_pages
        for s in np.nonzero(self.active)[0]:
            lo = int(self.lengths[s])
            hi = min(lo + self.block_span, self.max_position)
            if lo >= hi:
                continue
            row = self.page_table[s]
            first_pi = lo // ps
            page = int(row[first_pi])
            if page != sentinel and self.allocator.refcount[page] > 1:
                # the boundary page is shared: writing position `lo`
                # into it would corrupt the other holders — copy it
                (fresh,) = self.allocator.alloc(1, slot=int(s), cow=True)
                self._dispatch_copy(page, fresh)
                self.allocator.decref(page)
                row[first_pi] = fresh
                self.cow_copies += 1
            for pi in range(first_pi, (hi - 1) // ps + 1):
                if row[pi] == sentinel:
                    (fresh,) = self.allocator.alloc(1, slot=int(s))
                    row[pi] = fresh
        self._refresh_pool_stats()

    def _dispatch_copy(self, src, dst):
        try:
            self._pools = self._copy_fn(self._pools, np.int32(src),
                                        np.int32(dst))
        except BaseException:
            self.poisoned = True
            raise
        self.stats.dispatched()

    def dispatch_step(self):
        """Dispatch one block of ``steps_per_sync`` decode steps across
        every slot (call :meth:`reserve_block` first); the dense
        manager's contract, except that ``lengths`` advance at the
        readback."""
        extra = self._adapter_args(self.adapter_slots)
        try:
            if self.spec_tokens > 1:
                (self._pools, self._logits, self._key, self._table,
                 *toks) = self._step_fn(
                    self.params, self._pools, self._logits,
                    self.page_table, self.lengths, self.active,
                    self.temps, self._key, self._table, self._last_tok,
                    *extra)
            else:
                self._pools, self._logits, self._key, toks = self._step_fn(
                    self.params, self._pools, self._logits,
                    self.page_table, self.lengths, self.active,
                    self.temps, self._key, *extra)
        except BaseException:
            self.poisoned = True
            raise
        self.stats.dispatched()
        return DecodeBlock(toks, {})

    def read_step(self, block):
        """Same contract as the dense readback: (steps_per_sync,
        max_slots) host tokens, inactive rows junk — or the speculative
        variable-commit block with ``last_counts`` when
        ``spec_tokens`` > 1."""
        if self.spec_tokens > 1:
            toks = self._finish_spec_block(*block.toks)
        else:
            toks = jax.device_get(block.toks)  # ONE readback per block
            self.lengths[self.active] = np.minimum(
                self.lengths[self.active] + self.steps_per_sync,
                self.max_position)
        self._refresh_pool_stats()
        return toks

    def retire(self, slot):
        """Free a slot — active OR still pending (the scheduler cancels
        and preempts mid-prefill) — returning its page references to
        the allocator. Cached pages it wrote stay reclaimable for
        future prefix hits."""
        if self.active[slot]:
            self.active[slot] = False
        elif slot in self._pending:
            del self._pending[slot]
        else:
            raise ValueError(f"slot {slot} is not active")
        row = self.page_table[slot]
        for page in row[row != self.num_pages]:
            self.allocator.decref(int(page))
        row[:] = self.num_pages
        self.lengths[slot] = 0
        self.temps[slot] = 0.0
        self.adapter_slots[slot] = 0
        heapq.heappush(self._free, int(slot))
        self._occupied -= 1
        self._refresh_pool_stats()

    # -------------------------------------------------------- telemetry --
    def pool_stats(self):
        """Page-pool occupancy, fragmentation and prefix-cache counters
        (the scheduler publishes these on the per-engine registry).

        Returns the snapshot the owner thread rebinds after every
        admission/prefill/reserve/step/retire — ``engine.metrics()``
        reads it from foreign threads without ever touching the live
        allocator or pending-prefill structures mid-mutation."""
        return self._pool_snapshot

    def _refresh_pool_stats(self):
        """Owner thread only: recompute and publish the snapshot."""
        self._pool_snapshot = self._compute_pool_stats()

    def _compute_pool_stats(self):
        a = self.allocator
        in_use = a.in_use()
        frag = 0
        for s in range(self.max_slots):
            n_pages = int((self.page_table[s] != self.num_pages).sum())
            if not n_pages:
                continue
            used = (int(self.lengths[s]) if self.active[s]
                    else int(self._pending[s]["next"])
                    if s in self._pending else 0)
            frag += n_pages * self.page_size - used
        out = {
            "num_pages": self.num_pages,
            "page_size": self.page_size,
            "kv_dtype": "int8" if self.int8_kv
            else np.dtype(self._dtype).name,
            "kv_bytes_per_token": self._kv_token_bytes,
            "pool_bytes": self._kv_token_bytes * self.page_size
            * self.num_pages,
            # sharded view: what ONE chip pays per cached token / for
            # the whole pool (equals the unsharded numbers at tp=1)
            "tp_degree": self.tp,
            "mesh_devices": self.mesh_devices,
            "kv_bytes_per_token_per_chip": self._kv_token_bytes_per_chip,
            "pool_bytes_per_chip": self._kv_token_bytes_per_chip
            * self.page_size * self.num_pages,
            "pages_in_use": in_use,
            "pages_free": len(a._free),
            "pages_reclaimable": len(a._reclaimable),
            "page_occupancy": in_use / self.num_pages,
            "fragmentation_tokens": frag,
            "prefix_hits": self.prefix_hits,
            "prefix_misses": self.prefix_misses,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prefix_miss_tokens": self.prefix_miss_tokens,
            "prefix_evictions": a.evictions,
            "cow_copies": self.cow_copies,
        }
        if self.host_tier is not None:
            # single-lock tier snapshot — staged and resident are
            # disjoint owner states, so no page double-counts here
            for k, v in self.host_tier.stats().items():
                out["host_tier_" + k] = v
            out["host_tier_promoted_pages"] = self.host_promoted_pages
            out["host_tier_swap_stall_s"] = self.swap_stall_s
        return out
