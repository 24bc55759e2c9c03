"""ServingEngine: the public continuous-batching inference facade.

``ServingEngine(model, max_slots=8, max_queue=64)`` turns a causal LM
that speaks the model protocol (``serving/protocol.py``:
``models/gpt.py``, ``models/lfm2.py``) into a concurrent
serving system: callers ``submit()`` prompts from any thread and stream
tokens back, while one scheduler thread batches every live request into
a single masked decode dispatch per token step (see ``slots.py`` /
``scheduler.py`` for the two layers underneath, and docs/serving.md for
the architecture).

Contrast with ``generate()``: a second ``generate`` caller waits for
the whole first generation; a second ``submit`` caller waits only for
a free slot — and shares every subsequent dispatch.
"""

from __future__ import annotations

import time

from bigdl_tpu import obs
from bigdl_tpu.obs import reqtrace
from bigdl_tpu.serving.paging import PagedSlotManager, PagePoolExhausted
from bigdl_tpu.serving.protocol import check_model, need
from bigdl_tpu.serving.scheduler import QueueFullError, Request, Scheduler
from bigdl_tpu.serving.slots import SlotManager


class ServingEngine:
    """Continuous-batching engine over one model's KV-cache decode path.

    Parameters
    ----------
    model: a module that speaks the model protocol
        (``serving/protocol.py``, docs/serving.md): ``init_cache`` /
        ``prefill`` / ``decode_step`` / ``logits`` and what it tells of
        itself. Each optional feature below (``paged``, ``spec_tokens``,
        ``lora``, ``int8_weights``, ``int8_kv``, ``tp`` / ``mesh``,
        ``kv_snapshot``) is for a model whose ``serving_features`` carry
        it: asked of another, the constructor raises a ``TypeError``
        that names the feature.
    params: live parameters; defaults to ``model.params`` (built model).
    max_slots: concurrent in-flight requests (the preallocated cache's
        slot-table size — HBM cost scales with it).
    max_queue: waiting-queue bound; a full queue rejects ``submit`` with
        ``QueueFullError`` (backpressure, never unbounded buffering).
    prefill_window: max admissions batched into one prefill dispatch.
    admit_wait_s: time half of the prefill-batching window — with
        nothing decoding, hold admission up to this long so an arrival
        burst lands in one prefill instead of several partial ones
        (bounded TTFT cost; 0 disables).
    steps_per_sync: decode steps fused per dispatch between host syncs
        (>1 amortizes dispatch overhead; admission/retirement then
        happen at block granularity).
    top_k / top_p: engine-wide compile-time sampling truncation for
        requests with ``temperature > 0``.
    default_deadline_s: TTL applied to requests submitted without an
        explicit ``deadline_s`` (None = no deadline).
    failover: ``callable(victims, error)`` receiving every unfinished
        request if the decode loop exhausts its recovery budget — the
        ``EngineSupervisor`` hook (see docs/resilience.md).
    max_recoveries: in-place decode-loop recovery budget
        (``BIGDL_TPU_SERVING_MAX_RECOVERIES``, default 8).
    paged: use the paged K/V cache (``serving/paging.py``) — block
        allocator + page-table attention + chunked prefill + prefix
        sharing — instead of the dense slot table. Defaults to
        ``BIGDL_TPU_PAGED_KV`` (off: the dense table remains the
        default during the transition; docs/serving.md#paged-kv).
    page_size: tokens per K/V page (``BIGDL_TPU_PAGE_SIZE``, 16); must
        divide ``max_position``.
    kv_pages: page-pool size. Default is the dense-equivalent budget
        ``max_slots * max_position / page_size`` — shrink it (or grow
        ``max_slots``) to realize the paged memory win.
    prefill_chunk: chunked-prefill chunk width in tokens
        (``BIGDL_TPU_PREFILL_CHUNK``, 64).
    prefix_cache: share pages between requests with identical prompt
        prefixes (``BIGDL_TPU_PREFIX_CACHE``, on).
    spec_tokens: speculative-decoding draft length ``gamma`` applied to
        every decode block — an on-device n-gram draft proposes
        ``gamma`` tokens per slot and the target verifies them in one
        multi-token forward, committing 1..``gamma`` tokens per step for
        greedy requests (sampled requests commit exactly 1; temp-0
        streams stay token-identical; docs/serving.md#speculative-
        decoding). Defaults to the ``BIGDL_TPU_SPEC_DECODE`` /
        ``BIGDL_TPU_SPEC_TOKENS`` flags; 1 disables.
    int8_weights: serve from symmetric per-output-channel int8 weights
        (``nn/quantized.quantize_params``) — ~4x smaller parameter HBM,
        dequantize fused into each matmul. Defaults to
        ``BIGDL_TPU_INT8_WEIGHTS`` (off); docs/performance.md#int8.
    int8_kv: paged only — store K/V pages as int8 with per-page
        amax scales (quantize on write, dequantize in the gather), ~4x
        more tokens per byte of pool. Defaults to ``BIGDL_TPU_INT8_KV``
        (off).
    kv_bytes: paged only — size the page pool by HBM byte budget
        instead of page count (``paging.pages_for_budget``; accounts
        for ``int8_kv`` scale planes). Ignored when ``kv_pages`` is
        given.
    policy: a :class:`~bigdl_tpu.serving.control.ControlPolicy` enabling
        the serving control plane — priority classes with weighted-fair
        dequeue, per-client rate limits, and SLO-aware admission /
        shedding (docs/serving.md#control-plane). Defaults to the
        ``BIGDL_TPU_ADMISSION_SLO`` flag family; None keeps the plain
        FIFO path bit-identical to previous releases.
    kv_snapshot: paged only — crash-consistent recovery
        (``serving/snapshot.py``): asynchronously snapshot prefix-cached
        and hot K/V pages to ``snapshot_dir`` (content-addressed by the
        chained page digests) and journal admissions/deliveries, so an
        engine rebuilt over the same directory restores shared prefixes
        from disk instead of recomputing them. Defaults to
        ``BIGDL_TPU_KV_SNAPSHOT`` (off); docs/resilience.md#crash-
        consistent-recovery.
    snapshot_dir: store + journal directory
        (``BIGDL_TPU_SNAPSHOT_DIR``; required when ``kv_snapshot``).
    snapshot_interval_s: minimum seconds between snapshot passes
        (``BIGDL_TPU_SNAPSHOT_INTERVAL_S``, 0.5).
    snapshot_journal: journal file name inside ``snapshot_dir``
        (default ``journal.jsonl``). Engines SHARING a snapshot
        directory — fleet replicas pooling one content-addressed page
        store for cross-replica failover — must each use a distinct
        name: a journal is single-writer (its open-time compaction
        replaces the file), while the page store is safely shared.
    kv_host_tier: paged only — the tiered K/V memory middle rung
        (``serving/host_tier.py``): LRU-evicted pool pages demote their
        K/V planes into a bounded pinned-host pool (background copier,
        overlapped with decode) instead of being dropped, and prefix
        hits / preempted-stream resumes promote them back, giving the
        digest ladder HBM → host RAM → disk ``PageStore``. Defaults to
        ``BIGDL_TPU_KV_HOST_TIER`` (off — flag-off is byte-identical);
        docs/serving.md#tiered-kv.
    host_tier_bytes: host-tier byte budget
        (``BIGDL_TPU_KV_HOST_TIER_BYTES``; default 4x the pool's
        full-H host footprint — a 5x total envelope at fixed HBM).
    host_tier_prefetch: pages promoted one scheduler iteration AHEAD
        of the waiting queue's head admission, so the admission-time
        registry walk hits HBM instead of stalling on the swap
        (``BIGDL_TPU_KV_HOST_TIER_PREFETCH``, default 8; 0 disables
        the lookahead, promotion then happens at admission).
    tp: tensor-parallel degree — serve over a ``("tp",)`` device mesh
        (``parallel/layout.py``): weights Megatron-sharded, the K/V
        cache/pools head-sharded, per-chip HBM and matmul FLOPs cut by
        ``tp``, XLA inserting the ICI collectives. Temperature-0 output
        stays token-identical to the single-device engine. Defaults to
        ``BIGDL_TPU_SERVING_TP`` (off; tp=1 is bit-identical to a build
        without the mesh). Needs ``n_heads % tp == 0`` and ``tp``
        visible devices (docs/serving.md#sharded-serving).
    mesh: an explicit ``jax.sharding.Mesh`` to serve on instead of the
        default first-``tp``-devices sub-slice — how fleet replicas
        bind disjoint sub-slices (``serving.router.make_tp_factory``).
        Overrides ``tp``.
    lora: multi-tenant adapter multiplexing — serve many LoRA-tuned
        variants of the one base model from a paged, tiered,
        digest-addressed :class:`~bigdl_tpu.serving.adapters.AdapterPool`,
        every live request gathering its own adapter's low-rank delta
        inside the SAME batched decode dispatch (S-LoRA/Punica style;
        docs/serving.md#multi-tenant). Defaults to ``BIGDL_TPU_LORA``
        (off — flag-off builds no pool and is byte-identical).
    lora_rank: pool-wide adapter rank (``BIGDL_TPU_LORA_RANK``, 8);
        every registered adapter must match it.
    adapter_slots: device-pool capacity in adapters
        (``BIGDL_TPU_ADAPTER_SLOTS``, 8) — beyond it, unreferenced
        adapters LRU-demote through the tier ladder.
    adapters: optional ``{name: adapter}`` catalog registered at
        construction (``models/lora.init_adapter`` trees); more can be
        added later via :meth:`register_adapter`.
    adapter_host_bytes: pinned-host tier budget for evicted adapters
        (``BIGDL_TPU_ADAPTER_HOST_BYTES``, 0 = no adapter host tier) —
        the middle rung between the device pool and the shared
        ``PageStore``.
    """

    def __init__(self, model, params=None, max_slots=8, max_queue=64,
                 prefill_window=4, admit_wait_s=0.0, steps_per_sync=1,
                 top_k=None, top_p=None, seed=0, default_deadline_s=None,
                 failover=None, max_recoveries=None, paged=None,
                 page_size=None, kv_pages=None, prefill_chunk=None,
                 prefix_cache=None, policy=None, spec_tokens=None,
                 int8_weights=None, int8_kv=None, kv_bytes=None,
                 kv_snapshot=None, snapshot_dir=None,
                 snapshot_interval_s=None, snapshot_journal=None,
                 kv_host_tier=None, host_tier_bytes=None,
                 host_tier_prefetch=None, tp=None, mesh=None,
                 lora=None, lora_rank=None, adapter_slots=None,
                 adapters=None, adapter_host_bytes=None):
        from bigdl_tpu.utils.engine import get_flag
        params = getattr(model, "params", None) if params is None \
            else params
        if params is None:
            raise ValueError("setup()/build() the model before serving")
        check_model(model)
        # asked outright, whatever else is: both are read again, with
        # their flags, where the paged manager is built
        if int8_kv:
            need(model, "int8_kv")
        if kv_snapshot:
            need(model, "kv_snapshot")
        self.model = model
        self.default_deadline_s = default_deadline_s
        from bigdl_tpu.models.spec import spec_config
        if spec_tokens is None:
            # flag-driven default: BIGDL_TPU_SPEC_DECODE enables,
            # BIGDL_TPU_SPEC_TOKENS sizes the draft (models/spec.py)
            spec_tokens = spec_config()
        self.spec_tokens = max(1, int(spec_tokens))
        if self.spec_tokens > 1:
            need(model, "spec_tokens")
        if int8_weights is None:
            int8_weights = get_flag("BIGDL_TPU_INT8_WEIGHTS", False, bool)
        self.int8_weights = bool(int8_weights)
        if self.int8_weights:
            need(model, "int8_weights")
            from bigdl_tpu.nn.quantized import quantize_params
            params = quantize_params(params)
        # tensor-parallel layout — built AFTER int8 quantization so the
        # spec table covers the {"q", "scale"} leaves it introduces
        if tp is None:
            tp = get_flag("BIGDL_TPU_SERVING_TP", 0, int)
        tp = int(tp or 0)
        if mesh is not None or tp > 1:
            need(model, "tp")
            from bigdl_tpu.parallel.layout import ModelLayout, serving_mesh
            layout = ModelLayout(mesh if mesh is not None
                                 else serving_mesh(tp))
            if model.gpt.layers:
                layout.validate_heads(model.gpt.layers[0].attn.n_heads)
            params = layout.shard_params(model, params)
        else:
            layout = None
        self.layout = layout
        self.tp = 1 if layout is None else layout.tp
        # multi-tenant adapter pool — built AFTER int8 quantization and
        # layout sharding so its slabs match the final parameter leaves
        # (the pool quantizes/shards its own rows to agree with them)
        if lora is None:
            lora = get_flag("BIGDL_TPU_LORA", False, bool)
        if lora:
            need(model, "lora")
            from bigdl_tpu.serving.adapters import AdapterPool
            if lora_rank is None:
                lora_rank = get_flag("BIGDL_TPU_LORA_RANK", 8, int)
            if adapter_slots is None:
                adapter_slots = get_flag("BIGDL_TPU_ADAPTER_SLOTS",
                                         8, int)
            if adapter_host_bytes is None:
                adapter_host_bytes = get_flag(
                    "BIGDL_TPU_ADAPTER_HOST_BYTES", 0, int)
            if int(adapter_host_bytes or 0):
                from bigdl_tpu.serving.host_tier import HostPageTier
                adapter_tier = HostPageTier(int(adapter_host_bytes))
            else:
                adapter_tier = None
            self.adapter_pool = AdapterPool(
                params, int(adapter_slots), int(lora_rank),
                int8=self.int8_weights, host_tier=adapter_tier,
                layout=layout)
        else:
            if adapters:
                raise ValueError(
                    "adapters= needs the pool: pass lora=True or set "
                    "BIGDL_TPU_LORA")
            self.adapter_pool = None
        if paged is None:
            paged = get_flag("BIGDL_TPU_PAGED_KV", False, bool)
        self.paged = bool(paged)
        if self.paged:
            need(model, "paged")
            if page_size is None:
                page_size = get_flag("BIGDL_TPU_PAGE_SIZE", 16, int)
            if prefill_chunk is None:
                prefill_chunk = get_flag("BIGDL_TPU_PREFILL_CHUNK",
                                         64, int)
            if prefix_cache is None:
                prefix_cache = get_flag("BIGDL_TPU_PREFIX_CACHE",
                                        True, bool)
            if int8_kv is None:
                int8_kv = get_flag("BIGDL_TPU_INT8_KV", False, bool)
            if kv_bytes is not None and kv_pages is None:
                from bigdl_tpu.serving.paging import pages_for_budget
                # kv_bytes is a PER-CHIP budget: under a tp mesh each
                # chip holds 1/tp of the heads, so the pool gets tp
                # times the pages at the same per-chip spend
                kv_pages = pages_for_budget(
                    model, page_size, kv_bytes, int8=bool(int8_kv),
                    dtype=params["gpt"]["tok_emb"].dtype, tp=self.tp)
            if kv_snapshot is None:
                kv_snapshot = get_flag("BIGDL_TPU_KV_SNAPSHOT",
                                       False, bool)
            if kv_snapshot:
                from bigdl_tpu.serving.snapshot import KVSnapshot
                if snapshot_dir is None:
                    snapshot_dir = get_flag("BIGDL_TPU_SNAPSHOT_DIR",
                                            "", str)
                if not snapshot_dir:
                    raise ValueError(
                        "kv_snapshot needs a directory: pass "
                        "snapshot_dir= or set BIGDL_TPU_SNAPSHOT_DIR")
                if snapshot_interval_s is None:
                    snapshot_interval_s = get_flag(
                        "BIGDL_TPU_SNAPSHOT_INTERVAL_S", 0.5, float)
                self.snapshot = KVSnapshot(
                    snapshot_dir, interval_s=snapshot_interval_s,
                    journal_name=snapshot_journal)
            else:
                self.snapshot = None
            if kv_host_tier is None:
                kv_host_tier = get_flag("BIGDL_TPU_KV_HOST_TIER",
                                        False, bool)
            if kv_host_tier:
                from bigdl_tpu.serving.host_tier import (HostPageTier,
                                                         HostTierCopier)
                from bigdl_tpu.serving.paging import kv_token_bytes
                if host_tier_bytes is None:
                    host_tier_bytes = get_flag(
                        "BIGDL_TPU_KV_HOST_TIER_BYTES", 0, int)
                if host_tier_prefetch is None:
                    host_tier_prefetch = get_flag(
                        "BIGDL_TPU_KV_HOST_TIER_PREFETCH", 8, int)
                n_pages = (int(kv_pages) if kv_pages else
                           int(max_slots)
                           * (model.gpt.max_position // int(page_size)))
                page_host_bytes = kv_token_bytes(
                    model, bool(int8_kv),
                    params["gpt"]["tok_emb"].dtype) * int(page_size)
                if not host_tier_bytes:
                    # default budget: four pools' worth of demoted pages
                    # (full-H host layout) — a 5x total page envelope at
                    # fixed HBM spend
                    host_tier_bytes = 4 * page_host_bytes * n_pages
                self.host_tier = HostPageTier(host_tier_bytes)
                self._host_copier = HostTierCopier(self.host_tier)
            else:
                self.host_tier = None
                self._host_copier = None
            self.slots = PagedSlotManager(
                model, params, max_slots, num_pages=kv_pages,
                page_size=page_size, window=prefill_window,
                steps_per_sync=steps_per_sync,
                prefill_chunk=prefill_chunk, prefix_cache=prefix_cache,
                top_k=top_k, top_p=top_p, seed=seed,
                spec_tokens=self.spec_tokens, int8_kv=bool(int8_kv),
                page_store=(self.snapshot.store
                            if self.snapshot is not None else None),
                layout=layout, host_tier=self.host_tier,
                host_demote=(self._host_copier.submit
                             if self._host_copier is not None else None),
                host_tier_prefetch=(int(host_tier_prefetch or 0)
                                    if self.host_tier is not None
                                    else 0),
                adapter_pool=self.adapter_pool)
            if self.snapshot is not None:
                if self.snapshot.max_pages is None:
                    # bound the on-disk store to a small multiple of the
                    # pool: enough for several engine generations' prefix
                    # caches without growing unbounded
                    gc_pages = get_flag("BIGDL_TPU_KV_SNAPSHOT_GC_PAGES",
                                        0, int)
                    self.snapshot.max_pages = (
                        int(gc_pages) if gc_pages
                        else 4 * self.slots.num_pages)
                if self.host_tier is not None:
                    # a demoted page's disk copy may be its only durable
                    # one — gc must never collect a digest the volatile
                    # host tier still serves
                    self.snapshot.store.tier_resident = \
                        self.host_tier.hex_digests
        else:
            if kv_snapshot:
                raise ValueError("kv_snapshot requires paged=True (the "
                                 "store's unit of persistence is the "
                                 "K/V page)")
            if kv_host_tier:
                raise ValueError("kv_host_tier requires paged=True (the "
                                 "tier's unit of residency is the K/V "
                                 "page)")
            self.snapshot = None
            self.host_tier = None
            self._host_copier = None
            # mutually exclusive with the paged branch above: exactly one
            # manager (and one sampling generator) is ever built per engine
            # jaxlint: disable-next-line=key-reuse
            self.slots = SlotManager(model, params, max_slots,
                                     window=prefill_window,
                                     steps_per_sync=steps_per_sync,
                                     top_k=top_k, top_p=top_p, seed=seed,
                                     spec_tokens=self.spec_tokens,
                                     layout=layout,
                                     adapter_pool=self.adapter_pool)
        if self.adapter_pool is not None:
            if self.snapshot is not None:
                # adapters archive into the same content-addressed page
                # store as K/V — fleet siblings sharing the directory
                # can then cold-load by digest without a registration
                self.adapter_pool.store = self.snapshot.store
            for name, adapter in (adapters or {}).items():
                self.adapter_pool.register(name, adapter)
        if policy is None:
            from bigdl_tpu.serving.control import policy_from_flags
            policy = policy_from_flags()
        self.policy = policy
        self.scheduler = Scheduler(self.slots, max_queue=max_queue,
                                   admit_wait_s=admit_wait_s,
                                   failover=failover,
                                   max_recoveries=max_recoveries,
                                   policy=policy, snapshot=self.snapshot)
        # series label distinguishing this engine on the shared registry
        self.obs_label = self.scheduler.obs_label
        # /healthz liveness: the probe holds only a weakref — a dropped
        # engine prunes itself at the next health read, an explicit
        # shutdown unregisters (a cleanly-stopped engine is not a
        # failure the chaos harness should page on)
        import weakref
        ref = weakref.ref(self)
        label = self.obs_label

        def _health_probe():
            eng = ref()
            if eng is None:
                return None
            return {f"engine:{label}": eng.scheduler.is_alive()}

        self._health_probe = _health_probe
        obs.default_registry().register_probe(_health_probe)

    # ------------------------------------------------------------ serve --
    @property
    def stats(self):
        """The ``DecodeCounters`` — ``prefill_traces`` / ``step_traces``
        count compiles, ``dispatches`` counts executable launches."""
        return self.slots.stats

    def register_adapter(self, name, adapter):
        """Catalog a LoRA adapter (``models/lora.init_adapter`` tree)
        under ``name`` so ``submit(adapter=name)`` can decode against
        it. Returns its 16-byte content digest — also accepted (raw or
        hex) as the ``adapter=`` reference, which is how fleet siblings
        sharing a snapshot store address an adapter they never saw
        registered. Requires ``lora=True``."""
        if self.adapter_pool is None:
            raise ValueError(
                "register_adapter needs the adapter pool: build the "
                "engine with lora=True or set BIGDL_TPU_LORA")
        return self.adapter_pool.register(name, adapter)

    def submit(self, prompt, max_new_tokens, temperature=0.0,
               eos_token=None, deadline_s=None, priority="standard",
               client_id=None, adapter=None, trace=None):
        """Enqueue one generation request; returns its ``Request``
        handle immediately. Raises ``QueueFullError`` (backpressure) or
        ``EngineClosedError`` (after shutdown); prompts that cannot fit
        the cache are rejected up front. ``deadline_s`` is a TTL from
        now (defaults to the engine's ``default_deadline_s``); past it
        the request fails with ``DeadlineExceededError`` and frees its
        slot. ``priority`` / ``client_id`` feed the control plane when a
        policy is attached (weighted-fair dequeue, rate limits, SLO
        shedding — may additionally raise ``RateLimitedError`` /
        ``AdmissionRejectedError``); without one they are carried but
        inert. ``adapter`` names a registered LoRA adapter (or passes
        its digest, raw or hex) to decode against; None decodes the
        base model. Resolution happens at admission on the scheduler
        thread — an unknown adapter fails the REQUEST with
        ``AdapterLoadError``, never the submit call. ``trace`` carries
        an already-minted request-trace ID (the fleet mints one at
        routing); None mints a fresh one here (``obs.reqtrace``) —
        the handle's ``.trace`` follows the request through its whole
        lifecycle, across migration, into ``/requests``."""
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        req = Request(prompt, max_new_tokens, temperature=temperature,
                      eos_token=eos_token, deadline_s=deadline_s,
                      priority=priority, client_id=client_id,
                      adapter=adapter)
        if trace is None and reqtrace.enabled():
            trace = reqtrace.mint()
        req.trace = trace
        t = req.prompt.size
        pmax = self.model.max_position
        if t + req.max_new_tokens > pmax:
            raise ValueError(
                f"prompt ({t}) + max_new_tokens ({req.max_new_tokens}) "
                f"exceeds max_position ({pmax}); a static slot cache "
                f"cannot hold it")
        if self.paged:
            # worst-case page footprint of the whole generation: if the
            # pool could never hold it even empty, fail typed up front
            # instead of admitting a request that must be preempted
            # forever
            ps = self.slots.page_size
            worst = (t + req.max_new_tokens - 1) // ps + 1
            if worst > self.slots.num_pages:
                raise PagePoolExhausted(
                    f"request needs up to {worst} page(s) "
                    f"({t} prompt + {req.max_new_tokens} new tokens, "
                    f"page_size {ps}) but the pool holds only "
                    f"{self.slots.num_pages}")
        reqtrace.event(trace, "submit", request=req.id,
                       engine=self.obs_label, prompt_tokens=int(t),
                       max_new_tokens=int(req.max_new_tokens))
        with obs.span("serve/submit", request=req.id, trace=trace,
                      engine=self.scheduler.obs_label):
            return self.scheduler.submit(req)

    def resubmit(self, request):
        """Re-enqueue an existing (unfinished) handle on THIS engine —
        the supervisor's recovery route. The same ``Request`` object is
        reused, so the caller's stream stays attached; admission
        re-prefills from ``request.context()`` (prompt + tokens already
        delivered), so generation resumes exactly where it stopped and
        no token is delivered twice. Bypasses the queue bound: recovered
        requests must not be bounced by their own backlog."""
        if request.done.is_set():
            return request
        reqtrace.event(getattr(request, "trace", None), "resubmit",
                       request=request.id, engine=self.obs_label,
                       delivered=len(request.tokens))
        return self.scheduler.submit(request, force=True)

    def cancel(self, handle):
        """Cancel a submitted request (any thread): a waiting one fails
        immediately with ``RequestCancelledError``; an in-flight one is
        retired at the next block boundary, freeing its slot. Returns
        False when it had already finished."""
        return handle.cancel()

    def stream(self, handle):
        """Iterate a request's tokens as they are generated (blocking)."""
        return iter(handle)

    def result(self, handle, timeout=None):
        """Block for completion; returns prompt + generated tokens."""
        return handle.result(timeout)

    def generate(self, prompt, max_new_tokens, timeout=None, **kw):
        """Submit + block: the one-call convenience route.

        Unlike raw ``submit``, a full queue is retried with exponential
        backoff (``BIGDL_TPU_QUEUE_RETRIES``, default 3) before
        ``QueueFullError`` propagates, and a ``timeout`` that expires
        CANCELS the request — the slot is reclaimed, not leaked."""
        from bigdl_tpu.utils.engine import get_flag
        retries = get_flag("BIGDL_TPU_QUEUE_RETRIES", 3, int)
        backoff = get_flag("BIGDL_TPU_QUEUE_RETRY_BACKOFF_S", 0.05, float)
        for attempt in range(retries + 1):
            try:
                handle = self.submit(prompt, max_new_tokens, **kw)
                break
            except QueueFullError:
                if attempt >= retries:
                    raise
                time.sleep(backoff * (2 ** attempt))
        try:
            return self.result(handle, timeout=timeout)
        except TimeoutError:
            handle.cancel()
            raise

    # ---------------------------------------------------------- control --
    def metrics(self):
        """Live engine metrics: queue depth, slot occupancy, TTFT,
        decode throughput, admission counters, and the compile/dispatch
        gates (``utils.profiling.DecodeCounters``).

        A view over this engine's series on the obs default registry
        (the same numbers ``/metrics`` exposes, labeled
        ``engine="<id>"``); with the ``BIGDL_TPU_OBS`` kill switch off
        it falls back to the scheduler's plain attributes, which are
        maintained regardless."""
        sch, st = self.scheduler, self.slots.stats
        gates = {
            "prefill_traces": st["prefill_traces"],
            "step_traces": st["step_traces"],
            "dispatches": st["dispatches"],
            # the loop's dispatch-ahead: blocks dispatched with another
            # still in flight; slot-blocks computed for a stream the host
            # then found finished (EOS, cancel, deadline)
            "steps_ahead": st["steps_ahead"],
            "junk_slot_blocks": st["junk_slot_blocks"],
            "tp_degree": self.tp,
            "mesh_devices": (1 if self.layout is None
                             else self.layout.num_devices),
        }
        if self.paged:
            gates["copy_traces"] = st["copy_traces"]
            gates["preempted"] = sch.preempted
            gates.update(self.slots.pool_stats())
            if self.snapshot is not None:
                gates["snapshot_pages_written"] = \
                    self.snapshot.store.pages_written
                gates["snapshot_pages_restored"] = \
                    self.snapshot.store.pages_restored
                gates["restored_pages"] = self.slots.restored_pages
        if self.spec_tokens > 1:
            sl = self.slots
            gates["spec_proposed"] = sl.spec_proposed
            gates["spec_accepted"] = sl.spec_accepted
            gates["spec_rollbacks"] = sl.spec_rollbacks
            gates["spec_accept_rate"] = (
                sl.spec_accepted / sl.spec_proposed
                if sl.spec_proposed else 0.0)
        if self.adapter_pool is not None:
            for k, v in self.adapter_pool.stats().items():
                gates["adapter_" + k] = v
        if self.policy is not None:
            # control-plane counters are plain scheduler attributes in
            # both branches — the per-priority obs split lives on the
            # registry's bigdl_serving_shed_total family
            gates["shed"] = sch.shed
            gates["rate_limited"] = sch.rate_limited
            gates["downtiered"] = sch.downtiered
        if not obs.enabled():
            return {
                "queue_depth": sch.queue_depth(),
                "slot_occupancy": self.slots.occupancy(),
                "max_slots": self.slots.max_slots,
                "admitted": sch.admitted,
                "rejected": sch.rejected,
                "retired": sch.retired,
                "generated_tokens": sch.generated_tokens,
                "time_to_first_token_s": sch.ttft_avg(),
                "decode_tokens_per_sec": (
                    sch.generated_tokens / sch.step_seconds
                    if sch.step_seconds else 0.0),
                "failures": sch.failures,
                "recoveries": sch.recoveries,
                "quarantined": sch.quarantined,
                "cancelled": sch.cancelled,
                "deadline_exceeded": sch.deadline_expired,
                **gates,
            }
        o = sch._obs
        _, ttft_sum, ttft_count = o["ttft"].snapshot()
        step_s = o["step_seconds"].value
        toks = int(o["generated_tokens"].value)
        return {
            "queue_depth": int(o["queue_depth"].value),
            "slot_occupancy": int(o["slot_occupancy"].value),
            "max_slots": self.slots.max_slots,
            "admitted": int(o["admitted"].value),
            "rejected": int(o["rejected"].value),
            "retired": int(o["retired"].value),
            "generated_tokens": toks,
            "time_to_first_token_s": (
                ttft_sum / ttft_count if ttft_count else None),
            "decode_tokens_per_sec": toks / step_s if step_s else 0.0,
            "failures": int(o["failures"].value),
            "recoveries": int(o["recoveries"].value),
            "quarantined": int(o["quarantined"].value),
            "cancelled": int(o["cancelled"].value),
            "deadline_exceeded": int(o["deadline_exceeded"].value),
            **gates,
        }

    def is_alive(self):
        """True while the decode-loop thread runs (supervisor probe)."""
        return self.scheduler.is_alive()

    def shutdown(self, drain=True, timeout=None):
        """Stop accepting requests. ``drain=True`` (default) serves
        everything queued and in flight to completion first;
        ``drain=False`` cancels them with ``EngineClosedError``.
        Returns True when the scheduler thread exited, False when it is
        still alive after ``timeout`` (wedged — treat the engine as
        dead; see ``EngineSupervisor``). With KV snapshots enabled a
        clean exit takes one final forced snapshot (the next engine
        over this directory restores the whole prefix cache) and flushes
        the writer; a wedged loop skips it — the store is only ever
        touched from threads that own the dispatch path."""
        obs.default_registry().unregister_probe(self._health_probe)
        exited = self.scheduler.shutdown(drain=drain, timeout=timeout)
        snap = self.snapshot
        if snap is not None:
            if exited:
                try:
                    snap.snapshot(self.slots, force=True)
                except BaseException:
                    pass
                snap.flush()
            snap.close()
        if self._host_copier is not None:
            # after the scheduler stopped dispatching: drain pending
            # demotions (their slices are private buffers, safe to read
            # back any time) and stop the copier thread
            self._host_copier.close()
        return exited

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()
        return False
