"""Execution runtime: the TPU-native Engine.

Reference: ``utils/Engine.scala:39`` — a global runtime singleton that detects
(nExecutors, coresPerExecutor) from the Spark conf and owns the thread pools
layer forward/backward runs on. TPU-natively those responsibilities become:

- device/platform discovery (``jax.devices()``),
- construction of the ``jax.sharding.Mesh`` over ICI/DCN that the distributed
  optimizer shards over (replacing nodes*cores),
- the global dtype policy (bf16 compute on MXU vs f32 params),
- multi-host initialisation (``jax.distributed.initialize``) — the analog of
  ``Engine.init`` reading the cluster shape from SparkConf
  (``utils/Engine.scala:96,445-527``).

Thread pools disappear: intra-chip parallelism belongs to XLA, and
``Engine.model``/``Engine.default`` have no equivalent knobs worth exposing.
The reference's ``bigdl.*`` system-property flag system
(``docs/ScalaUserGuide/configuration.md:28-42``) maps to ``BIGDL_TPU_*``
environment variables read here.
"""

from __future__ import annotations

import logging
import os

logger = logging.getLogger("bigdl_tpu")


# --------------------------------------------------------------------- flags
# The reference's ``bigdl.*`` JVM-property flags
# (docs/ScalaUserGuide/configuration.md:28-42) become ``BIGDL_TPU_*`` env
# vars. Known flags (all optional):
#   BIGDL_TPU_PLATFORM              force jax platform ("tpu"/"cpu")
#   BIGDL_TPU_COMPUTE_DTYPE         "bfloat16" | "float32" (was bigdl.engineType)
#   BIGDL_TPU_ENABLE_NHWC           "1" -> zoo models default to NHWC, the
#                                   faster conv layout on TPU (channels map
#                                   to the 128-wide VPU/MXU lanes without a
#                                   relayout) (was bigdl.enableNHWC)
#   BIGDL_TPU_FAILURE_RETRY_TIMES   DistriOptimizer retry budget
#                                   (was bigdl.failure.retryTimes, default 5)
#   BIGDL_TPU_FAILURE_RETRY_INTERVAL  seconds: failures further apart than
#                                   this reset the retry counter (was
#                                   bigdl.failure.retryTimeInterval, 120)
#   BIGDL_TPU_PEAK_ICI_GBPS         per-link peak bus bandwidth used as the
#                                   allreduce-efficiency denominator
#   BIGDL_TPU_STEPS_PER_LOOP        default Optimizer steps_per_loop: K full
#                                   optimizer steps fused into one jitted
#                                   lax.scan dispatch over a [K, batch, ...]
#                                   superbatch (1 = classic per-step loop)
#   BIGDL_TPU_FLASH_ATTENTION       "1" -> MultiHeadAttention uses the
#                                   pallas flash kernel for local attention
#   BIGDL_TPU_LOG_FILE              redirect bigdl_tpu INFO logs to a file
#   BIGDL_TPU_OBS                   "0" -> kill switch for the telemetry
#                                   subsystem (bigdl_tpu.obs): metric
#                                   mutations and span recording become
#                                   no-ops (default on; docs/observability.md)
#   BIGDL_TPU_OBS_SPAN_CAPACITY     span ring-buffer size, default 8192
#                                   (oldest spans fall off)
#   BIGDL_TPU_ANOMALY_K             step-time anomaly threshold: a step
#                                   slower than K x rolling median is
#                                   flagged (default 3.0)
#   BIGDL_TPU_ANOMALY_WINDOW        rolling-median window in steps for the
#                                   anomaly detector (default 64)
#   BIGDL_TPU_REQ_TRACE             "0" -> disable per-request tracing,
#                                   the flight recorder and exemplars
#                                   (default on; host-side only
#                                   — docs/observability.md)
#   BIGDL_TPU_REQ_TRACE_CAPACITY    per-request timeline ring size,
#                                   default 256 events (oldest fall off,
#                                   counted as dropped)
#   BIGDL_TPU_FLIGHT_DIR            flight-recorder dump directory
#                                   (default <tmpdir>/bigdl_tpu_flight)
#   BIGDL_TPU_COORDINATOR           jax.distributed coordinator host:port
#   BIGDL_TPU_NUM_PROCESSES         total process count (multi-host)
#   BIGDL_TPU_PROCESS_ID            this process's id (multi-host)
#                                   (was utils/LoggerFilter.scala)
#   BIGDL_TPU_DISPATCH_AHEAD        training-loop loss-readback pipeline
#                                   depth (0 = synchronous, default 1)
#   BIGDL_TPU_ASYNC_CHECKPOINT      "0" -> checkpoint writes block the
#                                   driver instead of running write-behind
#                                   on a worker thread (default on)
#   BIGDL_TPU_SHARDED_CHECKPOINT    "1" -> DistriOptimizer writes per-host
#                                   shard files instead of gathered models
# Resilience (docs/resilience.md):
#   BIGDL_TPU_FAULT_PLAN            arm the deterministic fault-injection
#                                   harness, e.g. "seed=7;serving.step:
#                                   error:times=1;ckpt.write:corrupt"
#                                   (off unless set; resilience/faults.py)
#   BIGDL_TPU_PREEMPT_GUARD         "0" -> optimizers do NOT install the
#                                   SIGTERM preemption guard that drains,
#                                   checkpoints and raises
#                                   TrainingPreempted (default on)
#   BIGDL_TPU_SYNC_TIMEOUT_S        seconds: a blocking loss readback
#                                   slower than this increments
#                                   bigdl_sync_timeouts_total and logs a
#                                   straggler warning (0 = off, default)
#   BIGDL_TPU_QUEUE_RETRIES         ServingEngine.generate resubmission
#                                   budget on QueueFullError (default 3)
#   BIGDL_TPU_QUEUE_RETRY_BACKOFF_S initial generate() retry backoff,
#                                   doubling per attempt (default 0.05)
#   BIGDL_TPU_SERVING_MAX_RECOVERIES  scheduler engine-rebuild budget
#                                   before the engine fails over/halts
#                                   (default 8)
# Paged K/V serving (docs/serving.md#paged-kv):
#   BIGDL_TPU_PAGED_KV              "1" -> ServingEngine defaults to the
#                                   paged K/V cache (block allocator +
#                                   page-table attention + chunked
#                                   prefill + prefix sharing) instead of
#                                   the dense slot table (default off)
#   BIGDL_TPU_PAGE_SIZE             tokens per K/V page; must divide the
#                                   model's max_position (default 16)
#   BIGDL_TPU_PREFILL_CHUNK         chunked-prefill width in tokens: one
#                                   chunk dispatch per scheduler
#                                   iteration, interleaved with decode
#                                   (default 64)
#   BIGDL_TPU_PREFIX_CACHE          "0" -> disable hash-keyed prefix
#                                   sharing of K/V pages between
#                                   requests with identical prompt
#                                   prefixes (default on)
# Speculative + int8 decoding (docs/serving.md#speculative-decoding):
#   BIGDL_TPU_SPEC_DECODE           "1" -> greedy generate() and the
#                                   serving engines draft tokens from an
#                                   on-device n-gram table and verify
#                                   them in one multi-token forward;
#                                   temperature-0 output stays
#                                   token-identical (default off)
#   BIGDL_TPU_SPEC_TOKENS           draft length gamma per speculative
#                                   iteration (default 4; read only when
#                                   speculation is on)
#   BIGDL_TPU_INT8_WEIGHTS          "1" -> ServingEngine serves from
#                                   symmetric per-output-channel int8
#                                   weights (nn.quantized
#                                   .quantize_params; default off)
#   BIGDL_TPU_INT8_KV               "1" -> the paged engine stores K/V
#                                   pages as int8 with per-page scale
#                                   planes: >= 1.9x pages at an equal
#                                   byte budget (default off)
# Pallas decode kernels (docs/performance.md#paged-attention-kernel):
#   BIGDL_TPU_PAGED_KERNEL          "1" -> paged decode / chunked prefill
#                                   attend DIRECTLY against the K/V page
#                                   pool with the pallas kernel
#                                   (ops/paged_attention.py): the page
#                                   table rides the scalar-prefetch
#                                   channel so no (slots, max_position)
#                                   gather ever materializes; composes
#                                   with _INT8_KV (in-kernel dequant) and
#                                   _SERVING_TP (head-local shard_map);
#                                   temperature-0 output stays
#                                   token-identical (default off: the
#                                   XLA gather path, bit-identical to
#                                   previous releases)
# Crash-consistent recovery (docs/resilience.md#crash-consistent-recovery):
#   BIGDL_TPU_KV_SNAPSHOT           "1" -> paged engines snapshot
#                                   prefix-cached / hot K/V pages and
#                                   journal requests so a supervisor
#                                   rebuild restores state from disk
#                                   instead of recomputing it
#                                   (default off; needs _SNAPSHOT_DIR)
#   BIGDL_TPU_SNAPSHOT_DIR          page store + request journal
#                                   directory (required when the
#                                   snapshot flag is on)
#   BIGDL_TPU_SNAPSHOT_INTERVAL_S   minimum seconds between snapshot
#                                   passes (default 0.5)
# Fleet failover (docs/resilience.md#fleet-failover):
#   BIGDL_TPU_FLEET_FAILOVER        "1" -> EngineFleet tracks replica
#                                   health, ejects unhealthy replicas
#                                   from the rendezvous ring (probation
#                                   + canary re-admission) and migrates
#                                   their live streams to survivors,
#                                   restoring K/V from the shared page
#                                   store (default off: routing is
#                                   bit-identical to previous releases)
#   BIGDL_TPU_FLEET_EJECT_FAILURES  consecutive submit failures that
#                                   eject a replica (default 3)
#   BIGDL_TPU_FLEET_HEDGE_S         seconds an interactive generate()
#                                   waits on a non-serving home replica
#                                   before racing a hedged copy on
#                                   another; first success wins, loser
#                                   cancelled (default 0 = off)
# Mesh-sharded serving (docs/serving.md#sharded-serving):
#   BIGDL_TPU_SERVING_TP            tensor-parallel degree N > 1 ->
#                                   ServingEngine shards weights and K/V
#                                   over an N-device ("tp",) mesh
#                                   (Megatron column/row split; K/V pools
#                                   on the head axis, 1/N bytes per
#                                   chip); n_heads must divide by N;
#                                   temperature-0 output stays
#                                   token-identical (default 0 = off,
#                                   the single-device path untouched)
# Serving control plane (docs/serving.md#control-plane):
#   BIGDL_TPU_ADMISSION_SLO         "1" -> ServingEngine attaches a
#                                   ControlPolicy: priority classes with
#                                   weighted-fair dequeue, SLO-aware
#                                   admission/shedding, per-client rate
#                                   limits (default off: plain FIFO,
#                                   bit-identical to the policy-free
#                                   path)
#   BIGDL_TPU_TTFT_SLO_INTERACTIVE_S  TTFT budget in seconds applied to
#                                   "interactive" requests without an
#                                   explicit deadline (default 1.0)
#   BIGDL_TPU_TTFT_SLO_STANDARD_S   same for "standard" (default 5.0);
#                                   best_effort carries no SLO — it is
#                                   the tier that gets shed to protect
#                                   the other two
#   BIGDL_TPU_RATE_LIMIT_RPS        per-client token-bucket refill rate,
#                                   requests/s; over-rate submits raise
#                                   RateLimitedError (default: no limit)
#   BIGDL_TPU_RATE_LIMIT_BURST      token-bucket capacity (default
#                                   2 * BIGDL_TPU_RATE_LIMIT_RPS)
# Tiered K/V memory (docs/serving.md#tiered-kv):
#   BIGDL_TPU_KV_HOST_TIER          "1" -> paged engines demote
#                                   LRU-evicted K/V pages into a bounded
#                                   pinned-host pool (background copier,
#                                   overlapped with decode) and promote
#                                   them back on prefix hit / preempted
#                                   resume — the digest ladder's middle
#                                   rung between HBM and the disk
#                                   PageStore (default off; flag-off is
#                                   byte-identical)
#   BIGDL_TPU_KV_HOST_TIER_BYTES    host-tier byte budget (default 4x
#                                   the pool's full-H host footprint —
#                                   a 5x total page envelope at fixed
#                                   HBM)
#   BIGDL_TPU_KV_HOST_TIER_PREFETCH pages promoted one scheduler
#                                   iteration ahead of the waiting
#                                   queue head's admission (default 8;
#                                   0 promotes at admission time)
#   BIGDL_TPU_KV_SNAPSHOT_GC_PAGES  PageStore gc cap in pages (default
#                                   4x the page pool); digests resident
#                                   in the host tier are exempt — the
#                                   disk copy of a swapped-out page is
#                                   its only durable one
# Multi-tenant adapter multiplexing (docs/serving.md#multi-tenant):
#   BIGDL_TPU_LORA                  "1" -> ServingEngine builds the
#                                   paged, digest-addressed LoRA
#                                   AdapterPool: register adapters, pass
#                                   submit(adapter=...), and every live
#                                   request gathers its own adapter's
#                                   low-rank delta inside the one
#                                   batched decode dispatch (default
#                                   off; flag-off builds no pool and is
#                                   byte-identical)
#   BIGDL_TPU_LORA_RANK             pool-wide adapter rank (default 8);
#                                   every registered adapter must match
#   BIGDL_TPU_ADAPTER_SLOTS         device-pool capacity in adapters
#                                   (default 8); beyond it unreferenced
#                                   adapters LRU-demote down the tier
#                                   ladder
#   BIGDL_TPU_ADAPTER_HOST_BYTES    pinned-host tier budget for evicted
#                                   adapters (default 0 = no adapter
#                                   host tier; they then demote straight
#                                   to the PageStore / registry)

_TRUTHY = {"1", "true", "yes", "on"}


def get_flag(name, default=None, cast=str):
    """Read a ``BIGDL_TPU_*`` env flag with a typed cast.

    ``cast=bool`` accepts 1/true/yes/on (case-insensitive). Malformed values
    fall back to ``default`` with a warning rather than crashing training.
    """
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        if cast is bool:
            return raw.strip().lower() in _TRUTHY
        return cast(raw)
    except (TypeError, ValueError):
        logger.warning("ignoring malformed flag %s=%r (want %s)",
                       name, raw, cast.__name__)
        return default


def default_data_format():
    """Zoo-model default image layout. NCHW matches the reference's
    ``DataFormat`` default; BIGDL_TPU_ENABLE_NHWC=1 flips to the
    TPU-preferred channels-last layout (was ``bigdl.enableNHWC``)."""
    return "NHWC" if get_flag("BIGDL_TPU_ENABLE_NHWC", False, bool) else "NCHW"


class _Engine:
    """Singleton runtime. Use the module-level ``Engine`` instance."""

    def __init__(self):
        self._initialized = False
        self._mesh = None
        self._node_number = 1
        self._core_number = 1
        self._compute_dtype = None  # lazily jnp.bfloat16 on TPU else float32

    # ------------------------------------------------------------------ init
    def init(self, platform: str | None = None,
             coordinator_address: str | None = None,
             num_processes: int | None = None,
             process_id: int | None = None):
        """Initialise the runtime (reference ``Engine.init``, ``Engine.scala:96``).

        ``platform`` may force "tpu"/"cpu"; multi-host args mirror
        ``jax.distributed.initialize`` and replace SparkConf cluster detection.
        Safe to call more than once (later calls are no-ops), like the
        reference's idempotent init.
        """
        if self._initialized:
            return self
        import jax

        platform = platform or get_flag("BIGDL_TPU_PLATFORM")
        if platform:
            # jax read JAX_PLATFORMS at import, so the config update is
            # what counts; the env var is for child processes. A forced
            # platform that is absent makes jax.devices() below raise
            # instead of quietly choosing another backend.
            os.environ["JAX_PLATFORMS"] = platform
            jax.config.update("jax_platforms", platform)
        log_file = get_flag("BIGDL_TPU_LOG_FILE")
        if log_file and not any(
                isinstance(h, logging.FileHandler)
                and getattr(h, "baseFilename", None) == os.path.abspath(log_file)
                for h in logger.handlers):
            # LoggerFilter analog (utils/LoggerFilter.scala:91): route
            # bigdl_tpu INFO logs to a file, keep the console clean
            handler = logging.FileHandler(log_file)
            handler.setFormatter(logging.Formatter(
                "%(asctime)s %(levelname)s %(name)s - %(message)s"))
            logger.addHandler(handler)
            logger.setLevel(logging.INFO)
            logger.propagate = False
        # the bigdl-tpu-run launcher passes the cluster shape via env
        # (scripts/spark-submit-with-bigdl.sh analog, bigdl_tpu/launcher.py)
        coordinator_address = (coordinator_address
                               or get_flag("BIGDL_TPU_COORDINATOR"))
        if num_processes is None:
            num_processes = get_flag("BIGDL_TPU_NUM_PROCESSES", None, int)
        if process_id is None:
            process_id = get_flag("BIGDL_TPU_PROCESS_ID", None, int)
        if coordinator_address is not None:
            jax.distributed.initialize(coordinator_address=coordinator_address,
                                       num_processes=num_processes,
                                       process_id=process_id)
        if get_flag("BIGDL_TPU_COMPILE_CACHE", True, bool):
            # persistent XLA compilation cache: repeat runs skip the
            # 20-40 s first-compile of each train/eval program (the
            # reference has no equivalent — MKL kernels need no compile;
            # XLA does, so warm-starting is part of Engine init here).
            # BIGDL_TPU_COMPILE_CACHE=0 disables; JAX_COMPILATION_CACHE_DIR
            # places the directory (utils/compile_cache.py).
            from bigdl_tpu.utils.compile_cache import enable_persistent_cache
            enable_persistent_cache()
        devices = jax.devices()
        # node = host (was: Spark executor), core = local chip (was: Xeon core)
        self._node_number = jax.process_count()
        self._core_number = jax.local_device_count()
        self._initialized = True
        logger.info("Engine initialised: %d process(es) x %d device(s), platform=%s",
                    self._node_number, self._core_number, devices[0].platform)
        return self

    def _ensure_init(self):
        if not self._initialized:
            self.init()

    # ------------------------------------------------------------ properties
    def node_number(self) -> int:
        self._ensure_init()
        return self._node_number

    def core_number(self) -> int:
        self._ensure_init()
        return self._core_number

    def device_count(self) -> int:
        self._ensure_init()
        import jax
        return jax.device_count()

    def is_tpu(self) -> bool:
        self._ensure_init()
        import jax
        return jax.devices()[0].platform == "tpu"

    # ----------------------------------------------------------------- mesh
    def create_mesh(self, axes=None, devices=None):
        """Build the device mesh the distributed optimizer shards over.

        Default: 1-D "data" mesh over all devices (the reference has DP only,
        SURVEY.md section 2.6). Pass ``axes={"data": -1, "model": 4}``-style
        dicts for dp x tp meshes; -1 infers the remaining factor.
        """
        self._ensure_init()
        import numpy as np
        import jax
        from jax.sharding import Mesh

        devices = np.asarray(devices if devices is not None else jax.devices())
        if axes is None:
            axes = {"data": devices.size}
        names, sizes = list(axes.keys()), list(axes.values())
        known = int(np.prod([s for s in sizes if s != -1]))
        sizes = [devices.size // known if s == -1 else s for s in sizes]
        mesh = Mesh(devices.reshape(sizes), axis_names=names)
        self._mesh = mesh
        return mesh

    def mesh(self):
        if self._mesh is None:
            self.create_mesh()
        return self._mesh

    def set_mesh(self, mesh):
        self._mesh = mesh

    # ---------------------------------------------------------- dtype policy
    def compute_dtype(self):
        import jax.numpy as jnp
        if self._compute_dtype is None:
            flag = get_flag("BIGDL_TPU_COMPUTE_DTYPE", None,
                            lambda s: jnp.dtype(s).type)
            if flag is not None:
                self._compute_dtype = flag
            else:
                self._compute_dtype = (jnp.bfloat16 if self.is_tpu()
                                       else jnp.float32)
        return self._compute_dtype

    def set_compute_dtype(self, dtype):
        self._compute_dtype = dtype

    def reset(self):
        """Test hook (reference: ``Engine.setNodeAndCore`` test override)."""
        self._initialized = False
        self._mesh = None
        self._compute_dtype = None


Engine = _Engine()
