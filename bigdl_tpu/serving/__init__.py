"""bigdl_tpu.serving — continuous-batching inference engine.

Iteration-level scheduling (Orca) + slot-managed KV cache (vLLM's
insight, dense-slot variant) over the ``models/gpt.py`` decode
primitives: N concurrent requests share one masked decode dispatch per
token step instead of serializing whole generations. See
docs/serving.md.
"""

from bigdl_tpu.serving.adapters import (  # noqa: F401
    AdapterColdError, AdapterLoadError, AdapterPool, AdapterPoolExhausted)
from bigdl_tpu.serving.control import (  # noqa: F401
    AdmissionRejectedError, AutoScaler, ControlPolicy, FairQueue,
    RateLimitedError, TokenBucket)
from bigdl_tpu.serving.engine import ServingEngine  # noqa: F401
from bigdl_tpu.serving.host_tier import (  # noqa: F401
    HostPageTier, HostTierCopier)
from bigdl_tpu.serving.paging import (  # noqa: F401
    PageAllocator, PagedSlotManager, PagePoolExhausted)
from bigdl_tpu.serving.router import EngineFleet  # noqa: F401
from bigdl_tpu.serving.scheduler import (  # noqa: F401
    DeadlineExceededError, EngineClosedError, EngineFailedError,
    QueueFullError, Request, RequestCancelledError, Scheduler)
from bigdl_tpu.serving.slots import SlotManager  # noqa: F401
from bigdl_tpu.serving.snapshot import (  # noqa: F401
    KVSnapshot, PageStore, RequestJournal, SnapshotError)
from bigdl_tpu.utils import profiling as _profiling

_profiling.install_trace_annotator()   # leaf spans enter the profiler's trace
