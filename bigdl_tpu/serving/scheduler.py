"""Iteration-level scheduler: FIFO admission, token-step loop, streaming.

The scheduler turns the :class:`~bigdl_tpu.serving.slots.SlotManager`
decode kernel into a serving system: requests are admitted into free
slots and retired on EOS/max-tokens at token-step granularity
(continuous batching), so a new arrival never waits for someone else's
whole generation — only for a free slot.

Thread model: ONE scheduler thread owns the SlotManager — every jit
dispatch happens there. ``submit`` only appends to the bounded waiting
deque under the condition lock, so arbitrary caller threads never touch
device state. Backpressure is explicit: a full waiting queue rejects
with :class:`QueueFullError` instead of buffering unboundedly, and each
request's token stream is a bounded queue sized by its own
``max_new_tokens``.

Dispatch-ahead (docs/serving.md): where the slot manager's next block
needs nothing of the last block's tokens (``SlotManager.runs_ahead``), the
loop keeps ONE block in flight: an iteration admits, dispatches block N+1
and only then reads back and delivers block N, so the host's round trip
lies behind the device's work. A stream that ends by count is taken out
of the table at the dispatch of its last block; an EOS, a cancel or a
deadline is seen a block late, and that slot's one junk block is dropped.

Failure model (docs/resilience.md): the decode loop never dies holding
requests. A step/admit exception triggers in-place recovery — the slot
table is rebuilt and every in-flight request re-prefilled from its full
context (prompt + tokens already delivered, so nothing is ever
re-streamed), group-bisecting to quarantine a poisoned request (only it
fails; the rest continue). A recovery budget bounds thrashing: past it
the loop fails every request CLEANLY (each handle resolves with an
error) and either hands them to an attached failover (the
``EngineSupervisor``) or marks itself failed. The loop publishes a
heartbeat each iteration so a supervisor can distinguish wedged from
idle. Requests carry optional deadlines and support ``cancel()``, both
enforced at block boundaries where the slot is actually freed.
"""

from __future__ import annotations

import collections
import itertools
import logging
import queue
import threading
import time

import numpy as np

from bigdl_tpu import obs
from bigdl_tpu.obs import reqtrace
from bigdl_tpu.resilience.faults import fault_point
from bigdl_tpu.serving.paging import PagePoolExhausted

logger = logging.getLogger("bigdl_tpu.serving")

# TTFT needs finer low-end resolution than the latency defaults: small
# models prefill in well under a millisecond on a warm executable.
TTFT_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0)
# the gap between two deliveries to one stream: a decode block (2.5 ms
# for a small model on the chip) up to a stall of seconds
TOKEN_GAP_BUCKETS = (0.0005, 0.001, 0.002, 0.003, 0.005, 0.0075, 0.01,
                     0.015, 0.02, 0.03, 0.05, 0.075, 0.1, 0.15, 0.25, 0.5,
                     1.0, 2.5, 5.0, 10.0)


class QueueFullError(RuntimeError):
    """The waiting queue is at ``max_queue`` — backpressure; retry later."""


class EngineClosedError(RuntimeError):
    """The engine is shut down (or the request was cancelled by it)."""


class EngineFailedError(EngineClosedError):
    """The decode loop exhausted its recovery budget and halted; new
    submissions fast-fail until a supervisor restarts the engine."""


class RequestCancelledError(RuntimeError):
    """The request was cancelled via ``Request.cancel()`` /
    ``ServingEngine.cancel()``; its slot has been freed."""


class DeadlineExceededError(RuntimeError):
    """The request's ``deadline_s`` TTL elapsed before completion; its
    slot has been freed."""


class _Halt(BaseException):
    """Internal: unwind the scheduler loop (clean exit / abandoned /
    gave up). Never escapes ``_loop``."""


_DONE = object()


class _Flight:
    """A decode block between its dispatch and its delivery: the slot
    manager's ``block``, and ``streams``, the ``(slot, request, length
    before the block)`` of every stream it decodes for. A stream that
    ends by count with this block leaves ``Scheduler._inflight`` at the
    dispatch and is held here alone until its tokens are delivered;
    ``takes`` is what each request gets of the block by count (the next
    dispatch reckons with it before the tokens are read). ``prefills``
    and ``prefill_positions`` count the prefill executables launched
    since the block before this one was dispatched, and the positions
    they computed: what the device has queued BEFORE this block, and so
    what lengthens the token gap that this block's delivery ends."""

    __slots__ = ("block", "streams", "takes", "prefills",
                 "prefill_positions")

    def __init__(self, block, streams, takes, prefills=0,
                 prefill_positions=0):
        self.block = block
        self.streams = streams
        self.takes = takes
        self.prefills = prefills
        self.prefill_positions = prefill_positions


class Request:
    """One generation request and its token stream.

    Returned by ``ServingEngine.submit`` as the caller's handle: iterate
    it for streaming tokens, or call :meth:`result` to block for the
    full sequence. ``deadline_s`` is a wall-clock TTL from submission;
    past it the scheduler fails the request with
    :class:`DeadlineExceededError` and frees its slot.

    ``priority`` (``interactive`` / ``standard`` / ``best_effort``) and
    ``client_id`` only matter to a scheduler constructed with a
    :class:`~bigdl_tpu.serving.control.ControlPolicy`: they drive
    weighted-fair dequeue, per-client rate limits, and which requests
    admission control sheds first (docs/serving.md).

    ``adapter`` selects the LoRA adapter this request decodes under
    (docs/serving.md#multi-tenant): a name registered with the engine's
    :class:`~bigdl_tpu.serving.adapters.AdapterPool`, a digest hex
    string, or the 16-byte digest itself; ``None`` is the base model.
    The reference resolves to a refcounted pool row at admission and
    releases when the request leaves the engine.
    """

    _ids = itertools.count()

    def __init__(self, prompt, max_new_tokens, temperature=0.0,
                 eos_token=None, deadline_s=None, priority="standard",
                 client_id=None, adapter=None):
        if priority not in ("interactive", "standard", "best_effort"):
            raise ValueError(f"unknown priority {priority!r}; expected "
                             f"interactive/standard/best_effort")
        self.priority = priority
        self.client_id = client_id
        self.adapter = adapter
        self.adapter_digest = None     # resolved at admission
        self._adapter_slot = 0         # pool row while in flight (0 = base)
        self._adapter_seed = None      # adapter-separated prefix chain seed
        self.id = next(Request._ids)
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        if self.prompt.size == 0:
            raise ValueError("empty prompt")
        if int(max_new_tokens) < 1:
            raise ValueError(f"max_new_tokens must be >= 1, "
                             f"got {max_new_tokens}")
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature or 0.0)
        self.eos_token = None if eos_token is None else int(eos_token)
        self.tokens = []
        # bounded by construction: at most max_new_tokens + end sentinel
        self._stream = queue.Queue(self.max_new_tokens + 1)
        self.error = None
        self.done = threading.Event()
        self.submitted_at = time.perf_counter()
        if deadline_s is not None and float(deadline_s) <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        self.deadline = (None if deadline_s is None
                         else self.submitted_at + float(deadline_s))
        # popped from the waiting queue for its first admission: splits
        # the time to the first token into the wait in the queue
        # (``serve/queue_wait``) and admission to first token
        # (``serve/first_token``); ``prefill_bucket`` is the padded
        # prompt length of that admission (dense path)
        self.admitted_at = None
        self.prefill_bucket = None
        self.first_token_at = None
        self.finished_at = None
        # the stamp of the delivery that last gave this request a token
        # (``Scheduler._deliver_block``); None again at every admission
        self.delivered_at = None
        # True when the slot table ran out of positions before
        # max_new_tokens: the request finished successfully but short
        # (force-retire instead of clamped-position junk)
        self.truncated = False
        self._cancelled = False
        self._scheduler = None
        # request-trace ID (obs/reqtrace.py): minted at engine/fleet
        # submit, carried through the journal and across migration so
        # every hop appends to ONE timeline
        self.trace = None

    # ----------------------------------------------- scheduler-side hooks --
    def _deliver(self, chunk):
        """Append a block's worth of tokens (list of ints) in one stream
        put — per-token puts are measurable host overhead at serving
        rates."""
        if self.first_token_at is None:
            self.first_token_at = time.perf_counter()
        self.tokens.extend(chunk)
        self._stream.put(chunk)

    def _finish(self, error=None):
        self.error = error
        self.finished_at = time.perf_counter()
        self._stream.put(_DONE)
        self.done.set()

    def context(self):
        """Prompt + every token already delivered — what a re-prefill
        after recovery (or a supervisor resubmission) feeds the model,
        so generation continues exactly where it stopped and no token is
        ever streamed twice."""
        if not self.tokens:
            return self.prompt
        return np.concatenate(
            [self.prompt, np.asarray(self.tokens, np.int32)])

    def remaining(self):
        return self.max_new_tokens - len(self.tokens)

    # ------------------------------------------------------- caller side --
    def cancel(self):
        """Best-effort cancel from any thread: a waiting request fails
        immediately with :class:`RequestCancelledError`; an in-flight
        one is retired at the next block boundary (freeing its slot).
        Returns False when the request had already finished."""
        if self.done.is_set():
            return False
        self._cancelled = True
        sch = self._scheduler
        if sch is not None:
            sch.cancel(self)
        return True

    def __iter__(self):
        """Stream tokens as they are generated (blocking iterator); a
        cancelled/failed request raises its error after the last token."""
        while True:
            item = self._stream.get()
            if item is _DONE:
                break
            yield from item
        if self.error is not None:
            raise self.error

    def result(self, timeout=None):
        """Block until finished; returns prompt + generated tokens as one
        int32 array (the ``generate()`` output shape, minus the batch
        dim). On ``TimeoutError`` the request KEEPS its slot — call
        :meth:`cancel` to reclaim it (``ServingEngine.generate`` and
        ``PredictionService.generate`` do so automatically)."""
        if not self.done.wait(timeout):
            raise TimeoutError(f"request {self.id} still in flight after "
                               f"{timeout}s")
        if self.error is not None:
            raise self.error
        return np.concatenate(
            [self.prompt, np.asarray(self.tokens, np.int32)])


class Scheduler:
    """FIFO admission + iteration-level decode loop (see module docstring).

    Owns the background thread; constructed (and shut down) by
    ``ServingEngine``. ``failover(victims, error)``, when given, receives
    every unfinished request instead of their being failed when the loop
    gives up — the ``EngineSupervisor`` hook. ``max_recoveries`` bounds
    in-place recoveries over the scheduler's life (default
    ``BIGDL_TPU_SERVING_MAX_RECOVERIES``, 8).
    """

    _obs_ids = itertools.count()

    def __init__(self, slots, max_queue=64, admit_wait_s=0.0,
                 obs_label=None, failover=None, max_recoveries=None,
                 policy=None, snapshot=None):
        from bigdl_tpu.utils.engine import get_flag
        self.slots = slots
        # crash-consistent recovery (serving/snapshot.py): admissions,
        # delivered offsets, and retirements journal through `snapshot`;
        # the loop ticks asynchronous page snapshots after each block
        self._snap = snapshot
        self.max_queue = int(max_queue)
        self.admit_wait_s = float(admit_wait_s)
        # policy=None keeps the plain FIFO deque — bit-identical to the
        # pre-control-plane scheduler. With a ControlPolicy the queue is
        # a weighted-fair queue and submit() consults the policy
        # (rate limits, SLO admission) under the same condition lock.
        self._policy = policy
        self._waiting = (collections.deque() if policy is None
                         else policy.make_queue())
        self._cond = threading.Condition()
        self._accepting = True
        self._drain = True
        self._abandoned = False
        self._failover = failover
        self.failed = None
        if max_recoveries is None:
            max_recoveries = get_flag("BIGDL_TPU_SERVING_MAX_RECOVERIES",
                                      8, int)
        self.max_recoveries = int(max_recoveries)
        self._inflight = {}            # slot -> Request (loop thread only)
        # blocks dispatched and not yet delivered, oldest first: at most
        # one between iterations (two inside ``_step``), none for a
        # manager that cannot run ahead
        self._flight = collections.deque()
        # the token gap (docs/observability.md): the stamp of the last
        # delivery, and the prefill executables launched, with the
        # positions they computed, since the last decode block was
        # dispatched (``_dispatch_block`` moves them onto its ``_Flight``)
        self._delivered_at = None
        self._prefills = 0
        self._prefill_positions = 0
        for k in ("token_gaps", "token_gaps_after_prefill"):
            slots.stats.setdefault(k, 0)
        # requests the loop holds OUTSIDE _waiting/_inflight (a popped
        # admission batch, a recovery set): abandon()/_give_up() must see
        # them or a mid-admission crash would strand them
        self._limbo = []
        self.admitted = 0
        self.rejected = 0
        self.retired = 0
        self.generated_tokens = 0
        self.step_seconds = 0.0
        self.recoveries = 0
        self.quarantined = 0
        self.cancelled = 0
        self.deadline_expired = 0
        self.failures = 0
        self.preempted = 0
        self.shed = 0
        self.rate_limited = 0
        self.downtiered = 0
        # paged backpressure: after a preemption, hold new admissions
        # until a retirement frees pages (prevents the evicted stream
        # from immediately re-admitting into the same full pool)
        self._stall_admissions = False
        self._paged_published = {}
        self.heartbeat = time.monotonic()
        # count of loop iterations: every span the loop thread opens in
        # one iteration carries it as ``iter`` (the slot manager's too)
        self._iter = 0
        self._busy = False
        self._ttft_sum = 0.0
        # registry instruments: families are process-global, each engine
        # distinguishes its series by the ``engine`` label so many test
        # engines coexist on one default registry without clobbering
        if obs_label is None:
            obs_label = str(next(Scheduler._obs_ids))
        self.obs_label = str(obs_label)
        reg = obs.default_registry()
        lbl = ("engine",)
        e = self.obs_label
        self._obs = {
            "admitted": reg.counter(
                "bigdl_serving_admitted_total",
                "requests admitted into slots", lbl).labels(e),
            "rejected": reg.counter(
                "bigdl_serving_rejected_total",
                "requests rejected (queue full or engine closed)",
                lbl).labels(e),
            "retired": reg.counter(
                "bigdl_serving_retired_total",
                "requests served to completion", lbl).labels(e),
            "generated_tokens": reg.counter(
                "bigdl_serving_generated_tokens_total",
                "tokens delivered to callers", lbl).labels(e),
            "step_seconds": reg.counter(
                "bigdl_serving_step_seconds_total",
                "wall seconds inside decode-step dispatches", lbl).labels(e),
            "queue_depth": reg.gauge(
                "bigdl_serving_queue_depth",
                "requests waiting for a slot", lbl).labels(e),
            "slot_occupancy": reg.gauge(
                "bigdl_serving_slot_occupancy",
                "slots currently decoding", lbl).labels(e),
            "tokens_per_sec": reg.gauge(
                "bigdl_serving_decode_tokens_per_sec",
                "cumulative decode throughput", lbl).labels(e),
            "ttft": reg.histogram(
                "bigdl_serving_ttft_seconds",
                "submit-to-first-token latency", lbl,
                buckets=TTFT_BUCKETS).labels(e),
            "token_gap": reg.histogram(
                "bigdl_serving_token_gap_seconds",
                "gap between two consecutive deliveries of tokens to one "
                "stream (the inter-token latency at the loop)", lbl,
                buckets=TOKEN_GAP_BUCKETS).labels(e),
            "failures": reg.counter(
                "bigdl_serving_failures_total",
                "decode-loop step/admit exceptions caught", lbl).labels(e),
            "recoveries": reg.counter(
                "bigdl_serving_recoveries_total",
                "in-place slot-table recoveries", lbl).labels(e),
            "quarantined": reg.counter(
                "bigdl_serving_quarantined_total",
                "poisoned requests failed alone by recovery", lbl).labels(e),
            "cancelled": reg.counter(
                "bigdl_serving_cancelled_total",
                "requests cancelled by their caller", lbl).labels(e),
            "deadline_exceeded": reg.counter(
                "bigdl_serving_deadline_exceeded_total",
                "requests failed by their deadline TTL", lbl).labels(e),
            "heartbeat": reg.gauge(
                "bigdl_serving_heartbeat_timestamp",
                "unix time of the loop's last liveness beat", lbl).labels(e),
            "tp_degree": reg.gauge(
                "bigdl_serving_tp_degree",
                "tensor-parallel degree of the engine's serving mesh "
                "(1 = unsharded single-device)", lbl).labels(e),
            "mesh_devices": reg.gauge(
                "bigdl_mesh_devices",
                "devices in the engine's serving mesh", lbl).labels(e),
        }
        # static for the engine's lifetime — set once at construction
        self._obs["tp_degree"].set(getattr(slots, "tp", 1))
        self._obs["mesh_devices"].set(getattr(slots, "mesh_devices", 1))
        if policy is not None:
            shed = reg.counter(
                "bigdl_serving_shed_total",
                "requests shed by admission control",
                ("engine", "priority"))
            self._obs.update({
                "shed_interactive": shed.labels(e, "interactive"),
                "shed_standard": shed.labels(e, "standard"),
                "shed_best_effort": shed.labels(e, "best_effort"),
                "rate_limited": reg.counter(
                    "bigdl_serving_rate_limited_total",
                    "requests rejected by per-client rate limits",
                    lbl).labels(e),
                "downtiered": reg.counter(
                    "bigdl_serving_downtiered_total",
                    "standard requests demoted to best_effort by SLO "
                    "admission", lbl).labels(e),
            })
        if getattr(slots, "paged", False):
            self._obs.update({
                "preempted": reg.counter(
                    "bigdl_serving_preempted_total",
                    "in-flight requests preempted by page exhaustion",
                    lbl).labels(e),
                "pages_in_use": reg.gauge(
                    "bigdl_serving_pages_in_use",
                    "K/V pages referenced by live streams", lbl).labels(e),
                "pages_total": reg.gauge(
                    "bigdl_serving_pages_total",
                    "K/V page pool size", lbl).labels(e),
                "page_occupancy": reg.gauge(
                    "bigdl_serving_page_occupancy",
                    "fraction of the K/V page pool in use", lbl).labels(e),
                "fragmentation_tokens": reg.gauge(
                    "bigdl_serving_kv_fragmentation_tokens",
                    "allocated-but-unused K/V token capacity",
                    lbl).labels(e),
                "prefix_hits": reg.counter(
                    "bigdl_serving_prefix_cache_hits_total",
                    "admissions that reused a cached prefix",
                    lbl).labels(e),
                "prefix_misses": reg.counter(
                    "bigdl_serving_prefix_cache_misses_total",
                    "admissions with no cached prefix", lbl).labels(e),
                "prefix_hit_tokens": reg.counter(
                    "bigdl_serving_prefix_hit_tokens_total",
                    "prompt tokens served from the prefix cache",
                    lbl).labels(e),
                "prefix_miss_tokens": reg.counter(
                    "bigdl_serving_prefix_miss_tokens_total",
                    "prompt tokens prefilled from scratch", lbl).labels(e),
                "kv_bytes_per_token": reg.gauge(
                    "bigdl_serving_kv_bytes_per_token",
                    "K/V bytes per cached token across all layers "
                    "(int8 pools include their scale planes)",
                    lbl).labels(e),
                "kv_bytes_per_token_per_chip": reg.gauge(
                    "bigdl_serving_kv_bytes_per_token_per_chip",
                    "K/V bytes ONE chip pays per cached token: 1/tp of "
                    "the global figure under a tensor-parallel mesh "
                    "(equal to it at tp=1)", lbl).labels(e),
            })
            if getattr(slots, "host_tier", None) is not None:
                # tiered K/V memory (docs/serving.md#tiered-kv): swap
                # rate, hit rate, residency and stall accounting for the
                # pinned-host middle rung
                self._obs.update({
                    "host_tier_demoted": reg.counter(
                        "bigdl_kv_host_tier_demoted_pages_total",
                        "evicted pool pages swapped out to the host "
                        "tier", lbl).labels(e),
                    "host_tier_promoted": reg.counter(
                        "bigdl_kv_host_tier_promoted_pages_total",
                        "pages swapped back into the pool from the host "
                        "tier", lbl).labels(e),
                    "host_tier_hits": reg.counter(
                        "bigdl_kv_host_tier_hits_total",
                        "promotion probes served by the host tier",
                        lbl).labels(e),
                    "host_tier_misses": reg.counter(
                        "bigdl_kv_host_tier_misses_total",
                        "promotion probes that fell through to the "
                        "PageStore / re-prefill rungs", lbl).labels(e),
                    "host_tier_evicted": reg.counter(
                        "bigdl_kv_host_tier_evicted_pages_total",
                        "resident pages dropped by the tier's own LRU "
                        "byte-budget eviction", lbl).labels(e),
                    "host_tier_corrupt": reg.counter(
                        "bigdl_kv_host_tier_corrupt_dropped_total",
                        "resident pages dropped on checksum mismatch "
                        "(degraded down the ladder)", lbl).labels(e),
                    "host_tier_resident_bytes": reg.gauge(
                        "bigdl_kv_host_tier_resident_bytes",
                        "pinned-host bytes the tier holds", lbl).labels(e),
                    "host_tier_resident_pages": reg.gauge(
                        "bigdl_kv_host_tier_resident_pages",
                        "pages resident in the host tier", lbl).labels(e),
                    "host_tier_stall": reg.counter(
                        "bigdl_kv_host_tier_swap_stall_seconds_total",
                        "owner-thread seconds spent on swap staging and "
                        "promotion fetches (the overlap residual)",
                        lbl).labels(e),
                })
            self._update_paged_gauges()
        if snapshot is not None:
            streams = reg.counter(
                "bigdl_recovery_streams_total",
                "recovered streams by mode: restore resumed from "
                "snapshotted K/V pages, reprefill recomputed",
                ("engine", "mode"))
            self._obs.update({
                "recovery_replayed": reg.counter(
                    "bigdl_recovery_replayed_tokens_total",
                    "context tokens recomputed (not restored) while "
                    "re-placing recovered streams", lbl).labels(e),
                "recovery_restore": streams.labels(e, "restore"),
                "recovery_reprefill": streams.labels(e, "reprefill"),
            })
        self._spec_published = {}
        if getattr(slots, "spec_tokens", 1) > 1:
            self._obs.update({
                "spec_proposed": reg.counter(
                    "bigdl_serving_spec_proposed_total",
                    "draft tokens proposed for verification",
                    lbl).labels(e),
                "spec_accepted": reg.counter(
                    "bigdl_serving_spec_accepted_total",
                    "draft tokens the target model accepted",
                    lbl).labels(e),
                "spec_rollbacks": reg.counter(
                    "bigdl_serving_spec_rollbacks_total",
                    "draft tokens rejected and rolled back",
                    lbl).labels(e),
                "spec_accept_rate": reg.gauge(
                    "bigdl_serving_spec_accept_rate",
                    "cumulative fraction of proposed draft tokens "
                    "accepted", lbl).labels(e),
            })
        self._thread = threading.Thread(target=self._loop,
                                        name="bigdl-tpu-serving",
                                        daemon=True)
        self._thread.start()

    # ------------------------------------------------------- caller side --
    def submit(self, request, force=False):
        """Enqueue a request (any thread). Raises ``EngineClosedError``
        after shutdown, ``EngineFailedError`` after the loop halted, and
        ``QueueFullError`` when the waiting queue is at capacity — the
        backpressure contract: the caller retries or sheds load, the
        engine never buffers unboundedly. ``force`` bypasses the queue
        bound (supervisor resubmission only — recovered requests must
        not be bounced by their own backlog) and the admission policy.

        With a :class:`~bigdl_tpu.serving.control.ControlPolicy`
        attached, submission additionally enforces per-client rate
        limits (:class:`~bigdl_tpu.serving.control.RateLimitedError`)
        and SLO-aware admission: a request whose predicted TTFT blows
        its budget is shed if best-effort
        (:class:`~bigdl_tpu.serving.control.AdmissionRejectedError`),
        demoted to best-effort if standard, or — if interactive —
        admitted while a queued lower-tier request is shed instead."""
        with self._cond:
            if self.failed is not None:
                self.rejected += 1
                self._obs["rejected"].inc()
                raise EngineFailedError(
                    f"serving engine failed: {self.failed!r}")
            if not self._accepting:
                self.rejected += 1
                self._obs["rejected"].inc()
                raise EngineClosedError("engine is shut down")
            if self._policy is not None and not force:
                self._control_locked(request)
            if not force and len(self._waiting) >= self.max_queue:
                self.rejected += 1
                self._obs["rejected"].inc()
                raise QueueFullError(
                    f"waiting queue full ({self.max_queue} requests); "
                    f"retry later")
            request._scheduler = self
            self._waiting.append(request)
            self._obs["queue_depth"].set(len(self._waiting))
            self._cond.notify()
        return request

    def _control_locked(self, request):
        """Admission policy for one incoming request (cond lock held).
        Raises the typed rejection, mutates ``request.priority`` on
        down-tier, or sheds a queued victim to admit an interactive
        request — see docs/serving.md."""
        from bigdl_tpu.serving.control import (
            AdmissionRejectedError, RateLimitedError)
        pol = self._policy
        if not pol.check_rate(request.client_id):
            self.rejected += 1
            self.rate_limited += 1
            self._obs["rejected"].inc()
            self._obs["rate_limited"].inc()
            raise RateLimitedError(
                f"client {request.client_id!r} exceeded its rate limit "
                f"({pol.rate_limit_rps}/s); retry later")
        now = time.perf_counter()
        budget = pol.budget_s(request, now=now)
        slo_blown = (budget is not None
                     and pol.predict_ttft(self) > budget)
        queue_full = len(self._waiting) >= self.max_queue
        if not slo_blown and not queue_full:
            return
        if request.priority == "best_effort" and slo_blown:
            self._count_shed_locked(request)
            raise AdmissionRejectedError(
                f"request {request.id} (best_effort) shed: predicted "
                f"TTFT exceeds its {budget:.3f}s budget")
        if request.priority == "standard" and slo_blown:
            request.priority = "best_effort"
            self.downtiered += 1
            self._obs["downtiered"].inc()
        # higher-tier request under pressure: make room by shedding the
        # newest queued strictly-lower-tier request (best_effort first)
        shed = getattr(self._waiting, "shed_lower", None)
        while shed is not None and (slo_blown or
                                    len(self._waiting) >= self.max_queue):
            victim = shed(request.priority)
            if victim is None:
                break
            self._count_shed_locked(victim)
            self._obs["queue_depth"].set(len(self._waiting))
            victim._finish(AdmissionRejectedError(
                f"request {victim.id} ({victim.priority}) shed from the "
                f"queue to admit higher-priority work"))
            slo_blown = False   # the freed headroom is the remedy

    def _pop_batch_locked(self, n, free):
        """Policy-aware admission pop (cond lock held): weighted-fair
        order via the FairQueue, except the LAST ``reserved_slots`` free
        slots are held back for ``interactive`` requests — a best-effort
        flood can fill the engine only up to the reservation line, so an
        interactive arrival never waits a full decode generation for a
        slot. Clamped to ``max_slots - 1`` so lower tiers still progress
        on a one-slot engine."""
        reserved = min(self._policy.reserved_slots,
                       self.slots.max_slots - 1)
        batch = []
        while len(batch) < n and self._waiting:
            if reserved and free - len(batch) <= reserved:
                r = self._waiting.pop_priority("interactive")
                if r is None:
                    break
                batch.append(r)
            else:
                batch.append(self._waiting.popleft())
        return batch

    def _count_shed_locked(self, r):
        self.rejected += 1
        self.shed += 1
        self._obs["rejected"].inc()
        counter = self._obs.get("shed_" + r.priority)
        if counter is not None:
            counter.inc()

    def cancel(self, request):
        """Cancel a request submitted to this scheduler (any thread).
        Waiting requests fail immediately; in-flight ones at the next
        block boundary. Returns False when already finished."""
        request._cancelled = True
        with self._cond:
            if request.done.is_set():
                return False
            try:
                self._waiting.remove(request)
            except ValueError:
                # in flight (or being admitted): the loop sweeps it at
                # its next block boundary
                self._cond.notify()
                return True
            self._obs["queue_depth"].set(len(self._waiting))
        self._swept(request,
                    RequestCancelledError(f"request {request.id} cancelled"))
        return True

    def queue_depth(self):
        with self._cond:
            return len(self._waiting)

    def ttft_avg(self):
        return (self._ttft_sum / self.retired) if self.retired else None

    def ttft_histogram(self):
        """The engine's TTFT histogram child on the obs registry (or
        None with telemetry off) — the public accessor fleet routers
        and autoscalers scrape instead of reaching into ``_obs``."""
        return self._obs.get("ttft")

    def is_alive(self):
        """True while the decode-loop thread runs."""
        return self._thread.is_alive()

    def heartbeat_age(self):
        """Seconds since the loop last proved liveness."""
        return time.monotonic() - self.heartbeat

    def shutdown(self, drain=True, timeout=None):
        """Stop accepting. ``drain=True`` serves every queued and
        in-flight request to completion before the loop exits;
        ``drain=False`` cancels them with ``EngineClosedError``. Joins
        the scheduler thread; returns True when it exited, False when it
        is still alive after ``timeout`` (wedged in a dispatch — the
        join did NOT succeed and the engine must be treated as dead)."""
        with self._cond:
            self._accepting = False
            self._drain = drain
            self._cond.notify()
        self._thread.join(timeout)
        if self._thread.is_alive():
            logger.warning(
                "scheduler thread still alive %s s after shutdown "
                "(wedged in a dispatch?); engine must be abandoned",
                timeout)
            return False
        return True

    def abandon(self):
        """Supervisor hand-off: stop this (possibly wedged) loop from
        ever touching its requests again and return the unfinished ones
        for resubmission elsewhere. The loop observes the flag at its
        next safe point and exits without finishing anything."""
        with self._cond:
            self._abandoned = True
            self._accepting = False
            pool = list(self._waiting) + self._limbo \
                + list(self._inflight.values()) + self._in_flight_only()
            self._waiting.clear()
            self._obs["queue_depth"].set(0)
            self._cond.notify()
        # _inflight/_limbo belong to the loop thread, but an abandoned
        # loop is either parked in a dispatch or about to observe the
        # flag and halt — it no longer delivers or finishes anything
        seen, victims = set(), []
        for r in pool:
            if r.id not in seen and not r.done.is_set():
                seen.add(r.id)
                # the row index is meaningless outside THIS engine's
                # adapter pool (and the wedged loop may still own the
                # pool's structures — don't touch them from here);
                # resubmission re-resolves from r.adapter
                r._adapter_slot = 0
                r.adapter_digest = None
                victims.append(r)
        return victims

    # ---------------------------------------------------- scheduler loop --
    def _loop(self):
        try:
            self._serve()
        except _Halt:
            pass
        except BaseException as e:   # safety net: nobody may hang
            logger.exception("scheduler loop died")
            try:
                self._give_up(e)
            except _Halt:
                pass

    def _beat(self, busy=None):
        if busy is not None:
            self._busy = busy
        self.heartbeat = time.monotonic()
        self._obs["heartbeat"].set(time.time())

    # ------------------------------------------- crash-consistent journal --
    @property
    def restore_active(self):
        """True while the slot manager is loading snapshotted pages —
        the supervisor's wedge detector extends its grace window."""
        return bool(getattr(self.slots, "restore_active", False))

    def _journal_admit(self, r):
        if self._snap is None:
            return
        try:
            self._snap.admit(r)
        except BaseException:
            logger.exception("journal admit failed (ignored)")

    def _journal_delivered(self, r, n):
        """Record ``n`` just-delivered tokens (the tail of ``r.tokens``)
        with their stream offset — replay is idempotent on offsets, so
        a torn tail or a crash between delivery and append can never
        double-deliver."""
        if self._snap is None or not n:
            return
        try:
            off = len(r.tokens) - n
            self._snap.delivered(r, off, r.tokens[off:])
        except BaseException:
            logger.exception("journal delivery failed (ignored)")

    def _journal_retire(self, r):
        """Tombstone a finished request — compaction keeps the WAL
        bounded, the store drops its page pins, and the adapter pool
        drops the request's row reference (this is the one hook every
        request passes through exactly when it leaves the engine)."""
        self._release_adapter(r)
        if self._snap is None:
            return
        try:
            self._snap.retire(r.id)
        except BaseException:
            logger.exception("journal retire failed (ignored)")

    # ------------------------------------------------- adapter multiplex --
    def _release_adapter(self, r):
        """Drop the request's adapter-pool row reference (idempotent;
        row 0 — the base model — carries no reference)."""
        row = getattr(r, "_adapter_slot", 0)
        if not row:
            return
        r._adapter_slot = 0
        pool = getattr(self.slots, "adapter_pool", None)
        if pool is not None:
            try:
                pool.release(row)
            except BaseException:
                logger.exception("adapter release failed (ignored)")

    def _resolve_adapter(self, r, allow_load=True):
        """Resolve + acquire the request's adapter pool row at the
        admission boundary (loop thread). Returns ``"ok"`` (row and
        chain seed set on the request), ``"requeue"`` (cold adapter
        past this iteration's load budget, or the pool transiently
        exhausted by in-flight references — the caller puts the
        request back at the queue front; decode is never stalled), or
        ``"failed"`` (the request was finished with a typed error).

        Cold loads are the chunked-prefill treatment applied to
        weights: at most ONE synchronous swap-in rides each scheduler
        iteration (``allow_load``), interleaved with decode blocks, so
        a tenant churning cold adapters cannot starve resident
        streams."""
        from bigdl_tpu.serving.adapters import (
            AdapterColdError, AdapterLoadError, AdapterPoolExhausted)
        if getattr(r, "adapter", None) is None:
            r._adapter_slot = 0
            r._adapter_seed = None
            return "ok"
        if r._adapter_slot:
            return "ok"                # re-placement: row still held
        pool = getattr(self.slots, "adapter_pool", None)
        if pool is None:
            self._fail_adapter(r, AdapterLoadError(
                f"request {r.id} names adapter {r.adapter!r} but the "
                f"engine has no adapter pool (BIGDL_TPU_LORA off)"))
            return "failed"
        try:
            digest = pool.resolve(r.adapter)
        except KeyError as e:
            self._fail_adapter(r, AdapterLoadError(
                f"request {r.id}: unknown adapter {r.adapter!r}"))
            logger.warning("unknown adapter for request %d: %r", r.id, e)
            return "failed"
        try:
            row = pool.acquire(digest, allow_load=allow_load)
        except AdapterColdError:
            reqtrace.event(r.trace, "adapter_cold", request=r.id,
                           engine=self.obs_label,
                           adapter=digest.hex() if isinstance(
                               digest, bytes) else str(digest))
            return "requeue"
        except AdapterPoolExhausted as e:
            if self._inflight:
                # every resident adapter is referenced by in-flight
                # work; a retirement frees a row — requeue, keep decoding
                return "requeue"
            self._fail_adapter(r, e)
            return "failed"
        except AdapterLoadError as e:
            self._fail_adapter(r, e)
            return "failed"
        r.adapter_digest = digest
        r._adapter_slot = int(row)
        from bigdl_tpu.serving.paging import chain_seed
        r._adapter_seed = chain_seed(digest)
        return "ok"

    def _fail_adapter(self, r, err):
        with self._cond:
            self.rejected += 1
        self._obs["rejected"].inc()
        r._finish(err)
        self._journal_retire(r)

    def _resolve_batch(self, batch):
        """Adapter-resolve a popped admission batch: one cold load
        budgeted per iteration; requeued requests go back to the queue
        FRONT in order. Returns the admissible sub-batch."""
        if all(getattr(r, "adapter", None) is None
               and not getattr(r, "_adapter_slot", 0) for r in batch):
            return batch               # pure-base batch: zero overhead
        pool = getattr(self.slots, "adapter_pool", None)
        loads0 = getattr(pool, "loads", 0)
        live, requeue = [], []
        for r in batch:
            allow = getattr(pool, "loads", 0) == loads0
            state = self._resolve_adapter(r, allow_load=allow)
            if state == "ok":
                live.append(r)
            elif state == "requeue":
                requeue.append(r)
        if requeue:
            with self._cond:
                self._waiting.extendleft(reversed(requeue))
                self._obs["queue_depth"].set(len(self._waiting))
        return live

    def _maybe_snapshot(self, force=False):
        """Rate-limited asynchronous K/V page snapshot (loop thread,
        between dispatches): registered prefix-cache pages plus the
        full-block pages of live streams go to the store's writer
        thread. Never fails the loop."""
        snap = self._snap
        if snap is None or not getattr(self.slots, "paged", False):
            return
        if not (force or snap.due()):
            return
        try:
            self._settle()
            streams = []
            for s, r in list(self._inflight.items()):
                if self.slots.active[s]:
                    streams.append((r.id, r.context(), s,
                                    r._adapter_seed))
            with obs.span("serve/snapshot", streams=len(streams)):
                snap.snapshot(self.slots, streams, force=force)
        except BaseException:
            logger.exception("kv snapshot pass failed (serving continues)")

    def _count_resume(self, r):
        """Classify one re-placed stream after recovery: ``restore``
        when its whole context came out of the prefix cache / snapshot
        store (logits-only replay), ``reprefill`` otherwise; the
        recomputed remainder feeds the replayed-tokens counter."""
        if "recovery_restore" not in self._obs:
            return
        shared = int(getattr(self.slots, "last_admit_shared", 0))
        total = int(getattr(self.slots, "last_admit_total", 0))
        replayed = max(0, total - shared)
        if replayed:
            self._obs["recovery_replayed"].inc(replayed)
        if total and shared >= total:
            self._obs["recovery_restore"].inc()
        else:
            self._obs["recovery_reprefill"].inc()

    def _stamp_admitted(self, batch):
        """A batch was just popped for admission: one clock read gives
        each request its ``admitted_at`` and its ``serve/queue_wait``
        span, once a request (one put back by a cold adapter or a full
        page pool keeps its first pop, and waits again inside
        ``serve/first_token``)."""
        now = time.perf_counter()
        for r in batch:
            if r.admitted_at is None:
                r.admitted_at = now
                obs.record_span_at("serve/queue_wait", r.submitted_at, now,
                                   request=r.id, trace=r.trace,
                                   priority=r.priority)

    def _trace_admitted(self, r):
        """One ``admit`` timeline event (obs/reqtrace.py), carrying the
        prefix-restore split when the paged manager reports it —
        ``shared`` tokens came out of the cache/tier/store, the rest
        re-prefilled. ``delivered`` > 0 marks a re-placement (recovery,
        preemption resume, migration), not a first admission.
        ``queue_wait_s`` is the ``serve/queue_wait`` span's length, from
        the same clock read (``admitted_at``). Every admission passes
        here, so here the request forgets the delivery that last gave it
        a token: a stream placed again (a preemption, a recovery) has no
        token gap across that."""
        r.delivered_at = None
        reqtrace.event(
            r.trace, "admit", request=r.id, engine=self.obs_label,
            delivered=len(r.tokens),
            queue_wait_s=(None if r.admitted_at is None
                          else r.admitted_at - r.submitted_at),
            shared=int(getattr(self.slots, "last_admit_shared", 0)),
            total=int(getattr(self.slots, "last_admit_total", 0)))

    def _consume_resume_cb(self, r):
        """Fire-and-forget per-request resume classification: a fleet
        migrating ``r`` from a dead replica plants ``_resume_cb`` on the
        handle; the FIRST successful admission here consumes it, passing
        the slot manager's per-admission shared/total token counts so
        the fleet can count restore-vs-reprefill without touching
        loop-owned state (docs/resilience.md#fleet-failover)."""
        cb = r.__dict__.pop("_resume_cb", None)
        if cb is None:
            return
        try:
            cb(int(getattr(self.slots, "last_admit_shared", 0)),
               int(getattr(self.slots, "last_admit_total", 0)))
        except BaseException:
            logger.exception("resume callback failed (ignored)")

    def _serve(self):
        slots = self.slots
        while True:
            if self._abandoned:
                raise _Halt
            self._beat(busy=False)
            self._iter = slots.iter = it = self._iter + 1
            batch = []
            with self._cond:
                if (self._accepting and not self._waiting
                        and not self._inflight and not self._flight):
                    with obs.leaf_span("serve/idle", iter=it):
                        while (self._accepting and not self._waiting
                               and not self._inflight):
                            self._cond.wait()
                if self._abandoned:
                    raise _Halt
                if not self._accepting and not self._drain:
                    err = EngineClosedError("engine shut down")
                    while self._waiting:
                        w = self._waiting.popleft()
                        w._finish(err)
                        self._journal_retire(w)
                    # a block in flight is dropped with the streams it
                    # alone still held
                    ending = self._in_flight_only()
                    self._flight.clear()
                    for s in self._inflight:
                        slots.retire(s)
                    for r in [*self._inflight.values(), *ending]:
                        r._finish(err)
                        self._journal_retire(r)
                    self._inflight.clear()
                    self._obs["queue_depth"].set(0)
                    self._obs["slot_occupancy"].set(0)
                    return
                # time-based prefill batching: with nothing decoding yet,
                # hold admission up to admit_wait_s so a burst of arrivals
                # lands in ONE prefill dispatch instead of a ragged series
                # of partial batches (costs bounded TTFT, only when idle).
                # Before the sweep below, so that the two waits and the
                # pick are three disjoint leaves: a request whose deadline
                # ran out in the queue fails once the hold is over
                if (self.admit_wait_s > 0 and self._accepting
                        and not self._inflight and not self._flight
                        and 0 < len(self._waiting) < slots.window):
                    with obs.leaf_span("serve/idle", iter=it):
                        deadline = time.perf_counter() + self.admit_wait_s
                        remaining = self.admit_wait_s
                        while (self._accepting and remaining > 0
                               and len(self._waiting) < slots.window):
                            self._cond.wait(remaining)
                            remaining = deadline - time.perf_counter()
                with obs.leaf_span("serve/pick", iter=it) as pick:
                    self._sweep_waiting_locked()
                    queued = len(self._waiting)
                    pick.set(queued=queued, n=0)
                    if (not queued and not self._inflight
                            and not self._flight):
                        if not self._accepting:
                            return
                        continue
                    # FIFO admission, bounded by the prefill window and
                    # the free slots — one batched prefill dispatch per
                    # iteration
                    n = min(queued, slots.window, slots.free_slots())
                    if self._stall_admissions:
                        if self._inflight:
                            n = 0  # paged: wait for a retirement to free
                        else:      # pages before re-admitting
                            self._stall_admissions = False
                    if n and self._policy is not None:
                        batch = self._pop_batch_locked(n,
                                                       slots.free_slots())
                    else:
                        batch = [self._waiting.popleft() for _ in range(n)]
                    if batch:
                        self._limbo = list(batch)
                    self._obs["queue_depth"].set(len(self._waiting))
                    pick.set(n=len(batch))
            if batch:
                self._stamp_admitted(batch)
            self._beat(busy=True)
            paged = getattr(slots, "paged", False)
            with obs.leaf_span("serve/sweep", iter=it):
                self._sweep_inflight()
                if paged and getattr(slots, "host_tier", None) is not None:
                    self._prefetch_host_tier()
            # admission BEFORE the block's dispatch: an admitted request's
            # first token comes out of the very next block
            if batch:
                if paged:
                    self._admit_paged(batch)
                else:
                    self._admit(batch)
                self._limbo = []
                self._beat()
            if paged and slots.pending_prefills():
                # chunked prefill: ONE chunk dispatch per loop iteration,
                # interleaved with the decode block below so resident
                # streams keep emitting while long prompts trickle in
                try:
                    with obs.span("serve/prefill_chunk",
                                  pending=slots.pending_prefills()):
                        slots.prefill_tick()
                    self._prefill_launched(slots.window,
                                           slots.prefill_chunk)
                except _Halt:
                    raise
                except BaseException as e:
                    self.failures += 1
                    self._obs["failures"].inc()
                    self._recover(list(self._inflight.values()), e)
                    continue
                self._beat()
                self._update_paged_gauges()
            if not self._inflight and not self._flight:
                continue
            if paged and self._inflight:
                if not any(slots.active[s] for s in self._inflight):
                    continue       # everything in flight is still prefilling
                try:
                    slots.reserve_block()
                except _Halt:
                    raise
                except PagePoolExhausted as e:
                    self._preempt(e)
                    continue
                except BaseException as e:
                    self.failures += 1
                    self._obs["failures"].inc()
                    self._recover(list(self._inflight.values()), e)
                    continue
            try:
                if self._inflight:
                    fault_point("serving.step",
                                requests=tuple(
                                    r.id for r in self._inflight.values()))
                with obs.span("serve/step", iter=it) as step_span:
                    flight, toks = self._step(step_span)
            except _Halt:
                raise
            except BaseException as e:
                self.failures += 1
                self._obs["failures"].inc()
                self._recover(list(self._inflight.values()), e)
                continue
            if self._abandoned:
                raise _Halt
            self._beat()
            # the decode block's seconds are the span's: one interval, one
            # pair of clock reads (taken with telemetry off as well)
            dt = step_span.duration
            self.step_seconds += dt
            self._obs["step_seconds"].inc(dt)
            if flight is not None:
                with obs.leaf_span("serve/deliver", iter=it) as deliver:
                    deliver.set(**self._deliver_block(toks, flight,
                                                      at=deliver.start))
            with obs.leaf_span("serve/after", iter=it):
                self._maybe_snapshot()
                self._update_spec_gauges()
                if paged:
                    self._update_paged_gauges()
                if reqtrace.enabled():
                    with self._cond:
                        queued = len(self._waiting)
                    note = {"live": len(self._inflight),
                            "queued": queued, "step_s": dt,
                            "generated": self.generated_tokens}
                    if getattr(slots, "spec_proposed", 0):
                        note["spec_proposed"] = slots.spec_proposed
                        note["spec_accepted"] = slots.spec_accepted
                    reqtrace.default_flight().note_iteration(
                        self.obs_label, **note)

    # ---------------------------------------------------- the decode block --
    def _in_flight_only(self):
        """The unfinished requests that only a block in flight still
        holds: their last block was dispatched, so they left
        ``_inflight``, and its tokens are not delivered yet."""
        held = {id(r) for r in self._inflight.values()}
        only = []
        for flight in list(self._flight):
            for _, r, _ in flight.streams:
                if id(r) not in held and not r.done.is_set():
                    held.add(id(r))
                    only.append(r)
        return only

    def _step(self, span):
        """One iteration's decode work, inside its ``serve/step`` span:
        dispatch the next block for the live streams, THEN read back the
        block that was in flight, so the chip runs the one while the
        host waits for, delivers and sweeps after the other. Returns
        ``(flight, host tokens)`` of the block read back, ``(None,
        None)`` when none was (the first block of a busy stretch: it is
        read in the next iteration). A manager that cannot run ahead
        reads back the block it has just dispatched, which is the order
        of a loop without this. With no live stream left the iteration
        only drains the block in flight."""
        slots = self.slots
        old = self._flight[0] if self._flight else None
        if self._inflight:
            attn_blocks, attn_blocks_table = slots.attn_blocks()
            # of the block dispatched here ...
            span.set(live=len(self._inflight), ahead=int(old is not None),
                     kv_write=slots.kv_write,
                     kv_write_slots=slots.kv_write_slots(),
                     attn_read=slots.attn_read, attn_blocks=attn_blocks,
                     attn_blocks_table=attn_blocks_table,
                     sampler=slots.sampler, sampled=slots.sampled())
            new = self._dispatch_block(old)
            # (a model's own counts; with routed experts ``experts`` and
            # ``assignments``)
            span.set(**new.block.dispatched)
            if old is not None:
                slots.stats.add("steps_ahead", 1)
            elif not slots.runs_ahead:
                old = new
        else:
            span.set(ahead=0)
        if old is None:
            return None, None
        toks = self._read_oldest()
        # ... and of the block read back here, one older when ahead
        # (``experts_hit``, ``assignments_held``)
        span.set(**old.block.read)
        return old, toks

    def _read_oldest(self):
        """Read back the oldest block in flight and take it off the
        list; its host tokens, (steps_per_sync, max_slots)."""
        toks = self.slots.read_step(self._flight[0].block)
        with self._cond:
            self._flight.popleft()
        return toks

    def _settle(self):
        """Read back and deliver whatever block is in flight, so that
        what looks at ``lengths``, ``_inflight`` or the cache as a whole
        (a page snapshot, a preemption) sees the table as of the tokens
        the callers have. The next iteration starts a stretch again."""
        while self._flight:
            flight = self._flight[0]
            self._deliver_block(self._read_oldest(), flight)

    def _dispatch_block(self, old=None):
        """Dispatch one decode block and record whom it decodes for.
        Where the manager runs ahead, a stream that ends by count with
        this block (``max_new_tokens`` or the table's room: arithmetic
        on what the host has, less what ``old``, the block still in
        flight, will deliver) leaves the table HERE, so the block after
        its last token computes nothing for it and its slot takes the
        next admission; its tokens reach it at the delivery. The block
        takes the count of the prefills launched since the block before
        it with it: on the device they run before it, whichever
        iteration of the loop delivers it."""
        slots = self.slots
        ahead = slots.runs_ahead
        n_steps = slots.steps_per_sync
        owed = old.takes if old is not None else {}
        streams, takes, ending = [], {}, []
        for s, r in self._inflight.items():
            if not slots.active[s]:
                continue           # paged: still prefilling in chunks
            pre = int(slots.lengths[s])
            streams.append((s, r, pre))
            if ahead:
                left = r.remaining() - owed.get(r.id, 0)
                room = max(0, int(slots.max_position) - pre)
                takes[r.id] = take = min(n_steps, left, room)
                if take >= left or take >= room:
                    ending.append(s)
        flight = _Flight(slots.dispatch_step(), streams, takes,
                         self._prefills, self._prefill_positions)
        self._prefills = self._prefill_positions = 0
        with self._cond:
            self._flight.append(flight)
            for s in ending:
                del self._inflight[s]
        for s in ending:
            slots.retire(s)
        if ending:
            self._obs["slot_occupancy"].set(slots.occupancy())
        return flight

    # ------------------------------------------------------- admission ----
    def _prefill_launched(self, rows, bucket):
        """A prefill executable was launched over ``rows`` x ``bucket``
        positions: it stands on the device before the next decode block
        to be dispatched (``_dispatch_block`` takes the counts)."""
        self._prefills += 1
        self._prefill_positions += rows * bucket

    def _admit(self, batch):
        """One batched prefill dispatch; on failure, fall back to
        one-at-a-time admission so only the poisoned request fails."""
        slots = self.slots
        batch = self._expire_batch(batch)
        batch = self._resolve_batch(batch)
        if not batch:
            return
        try:
            fault_point("serving.admit",
                        requests=tuple(r.id for r in batch))
            with obs.span("serve/prefill", iter=self._iter,
                          n=len(batch)) as prefill:
                assigned = slots.admit(
                    [r.context() for r in batch],
                    [r.temperature for r in batch],
                    adapter_slots=[r._adapter_slot for r in batch])
                # what the dispatch was padded to, against what was asked
                # for: rows x bucket positions computed for ``tokens``
                rows, bucket = slots.last_prefill_shape
                self._prefill_launched(rows, bucket)
                prefill.set(rows=rows, bucket=bucket,
                            tokens=sum(r.prompt.size + len(r.tokens)
                                       for r in batch),
                            requests=[r.id for r in batch],
                            sampled=sum(r.temperature > 0.0
                                        for r in batch),
                            **slots.prefill_attrs)
                for r in batch:
                    r.prefill_bucket = bucket
        except _Halt:
            raise
        except BaseException as e:
            self.failures += 1
            self._obs["failures"].inc()
            logger.warning("batched admission failed (%r); "
                           "bisecting %d request(s)", e, len(batch))
            if slots.poisoned:
                self._recover(list(self._inflight.values()) + batch, e)
                return
            for r in batch:
                try:
                    fault_point("serving.admit", requests=(r.id,))
                    s, = slots.admit([r.context()], [r.temperature],
                                     adapter_slots=[r._adapter_slot])
                except _Halt:
                    raise
                except BaseException as e2:
                    if slots.poisoned:
                        rest = [x for x in batch
                                if x is not r and not x.done.is_set()]
                        self._quarantine(r, e2)
                        self._recover(
                            list(self._inflight.values()) + rest, e2)
                        return
                    self._quarantine(r, e2)
                else:
                    self._prefill_launched(*slots.last_prefill_shape)
                    with self._cond:
                        self._inflight[s] = r
                    self.admitted += 1
                    self._obs["admitted"].inc()
                    self._journal_admit(r)
                    self._consume_resume_cb(r)
                    self._trace_admitted(r)
        else:
            with self._cond:
                for r, s in zip(batch, assigned):
                    self._inflight[s] = r
            self.admitted += len(batch)
            self._obs["admitted"].inc(len(batch))
            for r in batch:
                self._journal_admit(r)
                self._consume_resume_cb(r)
                self._trace_admitted(r)
        self._obs["slot_occupancy"].set(slots.occupancy())

    def _admit_paged(self, batch):
        """Paged admission: per-request page allocation + pending
        prefill enqueue (host work only — ``prefill_tick`` dispatches
        the chunks). A ``PagePoolExhausted`` with other work holding
        the pool requeues the tail of the batch at the queue FRONT and
        stalls admission until a retirement frees pages; with the pool
        all to itself the request can never fit and fails typed."""
        slots = self.slots
        batch = self._expire_batch(batch)
        batch = self._resolve_batch(batch)
        for i, r in enumerate(batch):
            try:
                fault_point("serving.admit", requests=(r.id,))
                s = slots.admit_one(r.context(), r.temperature,
                                    adapter_slot=r._adapter_slot,
                                    seed=r._adapter_seed)
            except _Halt:
                raise
            except PagePoolExhausted as e:
                if self._inflight or i:
                    rest = [x for x in batch[i:] if not x.done.is_set()]
                    logger.warning(
                        "page pool exhausted admitting request %d; "
                        "requeueing %d request(s) until pages free",
                        r.id, len(rest))
                    with self._cond:
                        self._waiting.extendleft(reversed(rest))
                        self._obs["queue_depth"].set(len(self._waiting))
                    self._stall_admissions = True
                    break
                logger.warning("request %d cannot fit the page pool "
                               "even alone; failing it: %r", r.id, e)
                with self._cond:
                    self.rejected += 1
                self._obs["rejected"].inc()
                r._finish(e)
                self._journal_retire(r)
            except BaseException as e:
                self.failures += 1
                self._obs["failures"].inc()
                if slots.poisoned:
                    rest = [x for x in batch[i:]
                            if x is not r and not x.done.is_set()]
                    self._quarantine(r, e)
                    self._recover(
                        list(self._inflight.values()) + rest, e)
                    return
                self._quarantine(r, e)
            else:
                with self._cond:
                    self._inflight[s] = r
                self.admitted += 1
                self._obs["admitted"].inc()
                self._journal_admit(r)
                self._consume_resume_cb(r)
                self._trace_admitted(r)
        self._obs["slot_occupancy"].set(slots.occupancy())
        self._update_paged_gauges()

    def _prefetch_host_tier(self):
        """Swap-in lookahead (docs/serving.md#tiered-kv): promote the
        next waiting prompts' demoted prefix pages ONE scheduler
        iteration AHEAD of their admission, overlapped against this
        iteration's prefill/decode dispatches — the admission-time
        registry walk then hits HBM instead of stalling on the tier.
        Budgeted by ``host_tier_prefetch`` pages per iteration; only
        the queue's first two requests are peeked (FIFO admission means
        anything deeper is more than one iteration out)."""
        slots = self.slots
        left = int(getattr(slots, "host_tier_prefetch", 0))
        if left <= 0:
            return
        with self._cond:
            heads = [(w.prompt, getattr(w, "adapter", None)) for w in
                     itertools.islice(self._waiting, 2)]
        pool = getattr(slots, "adapter_pool", None)
        for prompt, ref in heads:
            if left <= 0:
                break
            seed = None
            if ref is not None:
                # adapter requests chain from an adapter-separated
                # seed; an unknown ref will fail at admission anyway
                if pool is None:
                    continue
                try:
                    from bigdl_tpu.serving.paging import chain_seed
                    seed = chain_seed(pool.resolve(ref))
                except KeyError:
                    continue
            try:
                left -= slots.prefetch_prefix(prompt, left, seed=seed)
            except BaseException:
                logger.exception(
                    "host-tier prefetch failed (admission will promote "
                    "or re-prefill instead)")
                return

    def _preempt(self, error):
        """Decode-time page exhaustion: preempt the NEWEST in-flight
        request — retire its slot (freeing its pages), requeue it at
        the queue front with its delivered tokens intact (re-admission
        resumes from ``context()``, nothing re-streamed) — so older
        streams keep decoding. A lone stream that cannot reserve its
        next positions can never finish: it fails typed instead."""
        slots = self.slots
        self._settle()
        if len(self._inflight) <= 1:
            for s, r in list(self._inflight.items()):
                with self._cond:
                    del self._inflight[s]
                    self.rejected += 1
                slots.retire(s)
                self._obs["rejected"].inc()
                reqtrace.event(r.trace, "failed", request=r.id,
                               engine=self.obs_label,
                               reason="page_pool_exhausted")
                r._finish(error)
                self._journal_retire(r)
            self._obs["slot_occupancy"].set(slots.occupancy())
            self._update_paged_gauges()
            return
        s = max(self._inflight, key=lambda s: self._inflight[s].id)
        with self._cond:
            r = self._inflight.pop(s)
        if getattr(slots, "host_tier", None) is not None:
            # swap-aware preemption (docs/serving.md#tiered-kv): register
            # the victim's written pages before retirement so eviction
            # demotes them through the host tier and its re-admission
            # promotes a full prefix hit instead of re-prefilling
            try:
                slots.preserve_stream(r.context(), s,
                                      seed=r._adapter_seed)
            except BaseException:
                logger.exception("preempt page preserve failed (stream "
                                 "will re-prefill)")
        slots.retire(s)
        # the victim leaves the engine until re-admission: its adapter
        # row must not stay referenced (it would pin the pool's LRU)
        self._release_adapter(r)
        self.preempted += 1
        self._obs["preempted"].inc()
        reqtrace.event(r.trace, "preempt", request=r.id,
                       engine=self.obs_label, delivered=len(r.tokens))
        reqtrace.default_flight().note_event(
            self.obs_label, "preempt", request=r.id,
            delivered=len(r.tokens))
        logger.warning("page pool exhausted (%s); preempting request %d "
                       "(%d tokens delivered, will resume)",
                       error, r.id, len(r.tokens))
        with self._cond:
            self._waiting.appendleft(r)
            self._obs["queue_depth"].set(len(self._waiting))
        self._stall_admissions = True
        self._obs["slot_occupancy"].set(slots.occupancy())
        self._update_paged_gauges()

    def _update_paged_gauges(self):
        """Publish the page-pool/prefix-cache snapshot on the
        per-engine registry series (paged engines only)."""
        if "pages_in_use" not in self._obs:
            return
        st = self.slots.pool_stats()
        o = self._obs
        o["pages_in_use"].set(st["pages_in_use"])
        o["pages_total"].set(st["num_pages"])
        o["page_occupancy"].set(st["page_occupancy"])
        o["fragmentation_tokens"].set(st["fragmentation_tokens"])
        o["kv_bytes_per_token"].set(st["kv_bytes_per_token"])
        o["kv_bytes_per_token_per_chip"].set(
            st["kv_bytes_per_token_per_chip"])
        for k in ("prefix_hits", "prefix_misses", "prefix_hit_tokens",
                  "prefix_miss_tokens"):
            delta = st[k] - self._paged_published.get(k, 0)
            if delta > 0:
                o[k].inc(delta)
            self._paged_published[k] = st[k]
        if "host_tier_resident_bytes" in o \
                and "host_tier_resident_bytes" in st:
            o["host_tier_resident_bytes"].set(
                st["host_tier_resident_bytes"])
            o["host_tier_resident_pages"].set(
                st["host_tier_resident_pages"])
            for obs_k, st_k in (
                    ("host_tier_demoted", "host_tier_demoted_pages"),
                    ("host_tier_promoted", "host_tier_promoted_pages"),
                    ("host_tier_hits", "host_tier_hits"),
                    ("host_tier_misses", "host_tier_misses"),
                    ("host_tier_evicted", "host_tier_evicted_pages"),
                    ("host_tier_corrupt", "host_tier_corrupt_dropped"),
                    ("host_tier_stall", "host_tier_swap_stall_s")):
                delta = st[st_k] - self._paged_published.get(st_k, 0)
                if delta > 0:
                    o[obs_k].inc(delta)
                self._paged_published[st_k] = st[st_k]

    def _update_spec_gauges(self):
        """Publish speculative-decoding counter deltas + the cumulative
        accept rate (engines with ``spec_tokens`` > 1 only)."""
        if "spec_proposed" not in self._obs:
            return
        sl = self.slots
        for k, v in (("spec_proposed", sl.spec_proposed),
                     ("spec_accepted", sl.spec_accepted),
                     ("spec_rollbacks", sl.spec_rollbacks)):
            delta = v - self._spec_published.get(k, 0)
            if delta > 0:
                self._obs[k].inc(delta)
            self._spec_published[k] = v
        if sl.spec_proposed:
            self._obs["spec_accept_rate"].set(
                sl.spec_accepted / sl.spec_proposed)

    # -------------------------------------------------------- delivery ----
    def _deliver_block(self, toks, flight, at=None):
        """Fan one step block's token columns out to the requests it was
        dispatched for (``flight.streams``: slot, request, the slot's
        length BEFORE the dispatch), retiring EOS/max-token
        completions. The length bounds each column to the positions the
        slot table can actually hold: a request whose ``prompt_len +
        generated`` reaches ``max_position`` is force-retired
        (``Request.truncated``) instead of being fed clamped-position
        junk. A request that finished while the block was in flight (an
        EOS in the block before, a cancel, a deadline) gets nothing of
        it: its column is junk, counted in ``junk_slot_blocks``.

        The token gap is measured here (docs/observability.md). ``at``
        stamps the delivery: its ``serve/deliver`` leaf's start, or one
        clock read where there is no leaf. A request keeps the stamp of
        the delivery that last gave it a token, so ``gap_streams``, the
        requests that get a token now AND got one in the delivery before
        (a first token has no gap, nor has a stream placed again in
        between), all waited the same ``gap_ms``: this stamp less the
        last. With ``steps_per_sync`` k a delivery hands a stream k
        tokens and the gap stays the delivery's. ``prefills`` and
        ``prefill_positions`` are the block's own (``_Flight``). Returns
        the ``serve/deliver`` span's attributes: ``tokens`` delivered,
        requests ``retired``, and the four above (``gap_ms`` and
        ``gap_streams`` only where a stream waited); with telemetry off,
        the first two alone."""
        done = []
        junk = 0
        tokens_before = self.generated_tokens
        timed = obs.enabled()
        if timed and at is None:
            at = time.perf_counter() - obs.default_tracer().epoch_perf
        last = self._delivered_at
        gap_streams = 0
        # speculative managers commit a VARIABLE count per slot each
        # block (1..block_span); last_counts bounds each column to the
        # tokens actually committed
        counts = getattr(self.slots, "last_counts", None)
        pmax = int(self.slots.max_position)
        for s, r, pre in flight.streams:
            if r.done.is_set():
                junk += 1
                continue
            # vectorized per-slot delivery: the block's token column,
            # truncated at max_new_tokens / first EOS (the tail past
            # either is junk the model kept decoding)
            col = toks[:, s] if counts is None else toks[:counts[s], s]
            col = col[:r.remaining()]
            finished = col.size == r.remaining()
            capped = False
            room = max(0, pmax - pre)
            if col.size >= room:
                col = col[:room]
                capped = True
            if r.eos_token is not None:
                hits = np.nonzero(col == r.eos_token)[0]
                if hits.size:
                    col = col[:int(hits[0]) + 1]
                    finished = True
                    capped = False
            if capped:
                finished = True
                if col.size < r.remaining():
                    r.truncated = True
            first = r.first_token_at is None
            r._deliver(col.tolist())
            if first and r.admitted_at is not None:
                obs.record_span_at(
                    "serve/first_token", r.admitted_at, r.first_token_at,
                    request=r.id, trace=r.trace,
                    prompt_tokens=int(r.prompt.size),
                    bucket=r.prefill_bucket)
            self._journal_delivered(r, col.size)
            if col.size:
                if timed:
                    if last is not None and r.delivered_at == last:
                        gap_streams += 1
                    r.delivered_at = at
                # stream offsets, not counts: the failover-continuity
                # test asserts a migrated stream's offsets tile
                # 0..total exactly once across BOTH replicas' events
                reqtrace.event(r.trace, "tokens", request=r.id,
                               engine=self.obs_label,
                               off=len(r.tokens) - int(col.size),
                               n=int(col.size))
            self.generated_tokens += col.size
            if finished:
                done.append((s, r))
        if junk:
            self.slots.stats.add("junk_slot_blocks", junk)
        for s, r in done:
            # a stream that ended by count left the table when its last
            # block was dispatched; one that ends by EOS leaves it here
            if self._inflight.get(s) is r:
                with self._cond:
                    del self._inflight[s]
                self.slots.retire(s)
            self.retired += 1
            self._stall_admissions = False   # pages/slots freed
            ttft = ((r.first_token_at - r.submitted_at)
                    if r.first_token_at is not None else 0.0)
            self._ttft_sum += ttft
            self._obs["retired"].inc()
            # exemplar: an outlier TTFT bucket keeps this trace ID, so
            # /metrics.json leads straight to the request's timeline
            self._obs["ttft"].observe(ttft, exemplar=r.trace)
            reqtrace.event(r.trace, "retire", request=r.id,
                           engine=self.obs_label, tokens=len(r.tokens),
                           ttft_s=ttft, truncated=r.truncated)
            r._finish()
            self._journal_retire(r)
        delivered = self.generated_tokens - tokens_before
        if delivered:
            self._obs["generated_tokens"].inc(delivered)
        if self.step_seconds:
            self._obs["tokens_per_sec"].set(
                self.generated_tokens / self.step_seconds)
        if done:
            self._obs["slot_occupancy"].set(self.slots.occupancy())
        attrs = {"tokens": int(delivered), "retired": len(done)}
        self._delivered_at = at if timed else None
        if timed:
            attrs.update(prefills=flight.prefills,
                         prefill_positions=flight.prefill_positions)
            if gap_streams:
                gap_s = at - last
                attrs.update(gap_ms=1e3 * gap_s, gap_streams=gap_streams)
                self._obs["token_gap"].observe(gap_s, n=gap_streams)
                stats = self.slots.stats
                stats.add("token_gaps", gap_streams)
                if flight.prefills:
                    stats.add("token_gaps_after_prefill", gap_streams)
        return attrs

    # -------------------------------------------- cancel/deadline sweeps --
    def _swept(self, r, err):
        reqtrace.event(r.trace,
                       "deadline" if isinstance(err, DeadlineExceededError)
                       else "cancelled",
                       request=r.id, engine=self.obs_label,
                       delivered=len(r.tokens))
        r._finish(err)
        self._journal_retire(r)
        # the cond's RLock makes the locked-sweep path re-entrant here;
        # cancel() reaches this from the caller thread, so the counters
        # need the guard
        if isinstance(err, DeadlineExceededError):
            with self._cond:
                self.deadline_expired += 1
            self._obs["deadline_exceeded"].inc()
        else:
            with self._cond:
                self.cancelled += 1
            self._obs["cancelled"].inc()

    def _sweep_waiting_locked(self):
        """Drop cancelled/expired waiting requests (cond lock held).
        Collect-then-remove (not a deque rebuild) so it works on both
        the plain deque and the control plane's ``FairQueue``."""
        if not self._waiting:
            return
        now = time.perf_counter()
        dead = [r for r in self._waiting
                if r._cancelled or (r.deadline is not None
                                    and now >= r.deadline)]
        if not dead:
            return
        for r in dead:
            self._waiting.remove(r)
            if r._cancelled:
                self._swept(r, RequestCancelledError(
                    f"request {r.id} cancelled"))
            else:
                self._swept(r, DeadlineExceededError(
                    f"request {r.id} exceeded its deadline after "
                    f"{now - r.submitted_at:.3f}s in queue"))
        self._obs["queue_depth"].set(len(self._waiting))

    def _expire_batch(self, batch):
        """Satellite of the deadline contract: a popped admission batch
        is re-checked at the PREFILL boundary — a request that expired
        (or was cancelled) while queued/batched fails here, before any
        prefill compute is spent on it. Returns the still-live batch."""
        now = time.perf_counter()
        live = []
        for r in batch:
            if r.done.is_set():
                continue
            if r._cancelled:
                self._swept(r, RequestCancelledError(
                    f"request {r.id} cancelled"))
            elif r.deadline is not None and now >= r.deadline:
                self._swept(r, DeadlineExceededError(
                    f"request {r.id} exceeded its deadline after "
                    f"{now - r.submitted_at:.3f}s before prefill"))
            else:
                live.append(r)
        return live

    def _sweep_inflight(self):
        """Retire cancelled/expired in-flight requests, freeing their
        slots (loop thread, between dispatches)."""
        now = time.perf_counter()
        hit = False
        for s, r in list(self._inflight.items()):
            if r._cancelled:
                err = RequestCancelledError(f"request {r.id} cancelled")
            elif r.deadline is not None and now >= r.deadline:
                err = DeadlineExceededError(
                    f"request {r.id} exceeded its deadline after "
                    f"{now - r.submitted_at:.3f}s "
                    f"({len(r.tokens)}/{r.max_new_tokens} tokens)")
            else:
                continue
            with self._cond:
                del self._inflight[s]
            self.slots.retire(s)
            self._swept(r, err)
            hit = True
        if hit:
            self._stall_admissions = False   # pages/slots freed
            self._obs["slot_occupancy"].set(self.slots.occupancy())

    # --------------------------------------------------------- recovery --
    def _quarantine(self, r, err):
        logger.warning("quarantining poisoned request %d: %r", r.id, err)
        self.quarantined += 1
        self._obs["quarantined"].inc()
        reqtrace.event(r.trace, "quarantine", request=r.id,
                       engine=self.obs_label, error=repr(err)[:120])
        r._finish(err)
        self._journal_retire(r)

    def _place(self, reqs, probe):
        """Rebuild the slot table and re-prefill ``reqs`` from their full
        context (idempotent: already-delivered tokens are part of the
        prompt now, never re-streamed). With ``probe=True`` also run one
        protected step block and deliver it. Returns the still-live
        requests."""
        slots = self.slots
        slots.reset()
        with self._cond:
            self._inflight.clear()
            self._flight.clear()
        # no stream's token gap spans a re-placement (``_trace_admitted``),
        # so the prefills before and during it are no delivery's
        self._prefills = self._prefill_positions = 0
        self._stall_admissions = False
        reqs = [r for r in reqs if not r.done.is_set()]
        # recovered adapter requests normally still hold their pool rows
        # (resolve is a no-op then); a supervisor resubmission arrives
        # row-less and re-resolves here
        reqs = self._resolve_batch(reqs)
        paged = getattr(slots, "paged", False)
        # restore accounting needs per-request admission (the slot
        # manager's last_admit_shared/total are per-admit_one); the
        # chunks stay batched everywhere else
        count = (self._snap is not None
                 and getattr(slots, "paged", False)
                 and "recovery_restore" in self._obs)
        i = 0
        while i < len(reqs):
            take = 1 if count else min(slots.window, slots.free_slots())
            chunk = reqs[i:i + take]
            fault_point("serving.admit",
                        requests=tuple(r.id for r in chunk))
            kw = {"adapter_slots": [r._adapter_slot for r in chunk]}
            if paged:
                kw["seeds"] = [r._adapter_seed for r in chunk]
            assigned = slots.admit([r.context() for r in chunk],
                                   [r.temperature for r in chunk], **kw)
            with self._cond:
                for r, s in zip(chunk, assigned):
                    self._inflight[s] = r
            for r in chunk:
                if count:
                    self._count_resume(r)
                self._consume_resume_cb(r)
                self._trace_admitted(r)
            i += len(chunk)
        if probe and self._inflight:
            fault_point("serving.step",
                        requests=tuple(r.id
                                       for r in self._inflight.values()))
            flight = self._dispatch_block()
            toks = self._read_oldest()
            if self._abandoned:
                raise _Halt
            self._beat()
            self._deliver_block(toks, flight)
            self._update_spec_gauges()
        self._obs["slot_occupancy"].set(slots.occupancy())
        self._update_paged_gauges()
        return list(self._inflight.values())

    def _recover(self, affected, error):
        """In-place recovery from a step/admit failure: reset the slot
        table, then group-bisect the affected requests — a group whose
        probe step fails is split until the poisoned request is alone
        and quarantined; everyone else resumes from their exact context.
        Past the recovery budget the loop gives up cleanly."""
        self.recoveries += 1
        self._obs["recoveries"].inc()
        if self.recoveries > self.max_recoveries:
            logger.error("recovery budget exhausted (%d > %d); halting",
                         self.recoveries, self.max_recoveries)
            self._give_up(error)
        # a block in flight goes with the table: its tokens are dropped
        # (never delivered, so never re-streamed) and the streams it alone
        # still held are re-placed with the rest, as one recovery
        held = {id(r) for r in affected}
        affected = affected + [r for r in self._in_flight_only()
                               if id(r) not in held]
        affected = [r for r in affected if not r.done.is_set()]
        logger.warning("recovering decode loop after %r: %d request(s) "
                       "to re-place (recovery %d/%d)", error,
                       len(affected), self.recoveries, self.max_recoveries)
        self._limbo = list(affected)
        with self._cond:
            self._inflight.clear()
            self._flight.clear()
        healthy = []
        groups = [affected] if affected else []
        probes = 0
        clean = not groups
        while groups:
            probes += 1
            if probes > 2 * len(affected) + 8:
                self._give_up(error)
            g = groups.pop(0)
            try:
                healthy = self._place(healthy + g, probe=True)
                clean = True
            except _Halt:
                raise
            except BaseException as e:
                clean = False
                g = [r for r in g if not r.done.is_set()]
                healthy = [r for r in healthy if not r.done.is_set()]
                if len(g) <= 1:
                    if g:
                        self._quarantine(g[0], e)
                else:
                    mid = len(g) // 2
                    groups[:0] = [g[:mid], g[mid:]]
        if not clean:
            try:
                self._place(healthy, probe=False)
            except _Halt:
                raise
            except BaseException as e:
                self._give_up(e)
        self._limbo = []
        self._beat()

    def _give_up(self, error):
        """Terminal failure: resolve EVERY outstanding handle (failover
        or error — never a hang), mark the scheduler failed, halt the
        loop."""
        with self._cond:
            self._accepting = False
            self.failed = error
            pool = list(self._waiting) + self._limbo \
                + list(self._inflight.values()) + self._in_flight_only()
            self._waiting.clear()
            self._inflight.clear()
            self._flight.clear()
            # decide the handoff atomically with the drain: the monitor
            # may see ``failed`` and call abandon() the moment the lock
            # drops — it will collect nothing (the pool is already
            # drained here), and the restart path merges whatever the
            # failover banks, deduped by request id
            handoff = self._failover is not None and not self._abandoned
            self._obs["queue_depth"].set(0)
        self._limbo = []
        seen, victims = set(), []
        for r in pool:
            if r.id not in seen and not r.done.is_set():
                seen.add(r.id)
                # leaving this engine either way (failover resubmits on
                # a sibling with its OWN pool; terminal failure retires)
                self._release_adapter(r)
                r.adapter_digest = None
                victims.append(r)
        try:
            self.slots.reset()
        except BaseException:
            logger.exception("slot-table reset failed during give-up")
        self._obs["slot_occupancy"].set(0)
        if handoff:
            logger.warning("handing %d request(s) to failover after %r",
                           len(victims), error)
            for r in victims:
                reqtrace.event(r.trace, "failover_handoff", request=r.id,
                               engine=self.obs_label,
                               delivered=len(r.tokens))
            try:
                self._failover(victims, error)
                victims = []
            except BaseException:
                logger.exception("failover handler failed; "
                                 "failing requests instead")
        err = EngineFailedError(f"serving engine failed: {error!r}")
        err.__cause__ = error
        for r in victims:
            reqtrace.event(r.trace, "failed", request=r.id,
                           engine=self.obs_label, error=repr(error)[:120])
            r._finish(err)
            # failover-banked victims stay LIVE in the journal (they
            # resubmit elsewhere); only terminally-failed ones retire
            self._journal_retire(r)
        raise _Halt
