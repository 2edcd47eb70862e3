"""paddle_tpu.serving — continuous-batching LLM serving on TPU.

A paged KV-cache pool (PagedAttention, SOSP '23) plus an
iteration-level continuous-batching engine (Orca, OSDI '22) whose
decode step is one compiled program over a fixed slot axis — request
churn changes array values, never shapes, so nothing ever retraces.
See SERVING.md for the design and the determinism contract.

    from paddle_tpu.serving import ServingEngine, SamplingParams
    eng = ServingEngine(model, num_pages=64, page_size=16, max_slots=4)
    rid = eng.add_request(prompt_ids, max_new_tokens=32, eos_token_id=2)
    for ev in eng.stream():
        print(ev["rid"], ev["token"])

For fault tolerance, N replicas go behind a :class:`FleetRouter`
(fleet.py — SERVING.md "Engine fleet & failover"): health-checked
least-loaded routing with prefix-cache affinity, circuit-broken
placement, and deterministic failover replay with exactly-once client
streams.
"""

from .engine import BrownoutConfig, ServingEngine
from .errors import (AdmissionShedError, EngineDrainingError,
                     FleetOverloadedError, LatentCacheError,
                     QueueFullError,
                     RecurrentStateError, ReplicaSpawnError,
                     RequestTooLargeError,
                     SchedulerStalledError, ServingError, StaleEpochError,
                     TPConfigError, TransportError)
from .fleet import FleetRequest, FleetRouter
from .transport import (ChaosTransport, EngineServer, LoopbackTransport,
                        Message, Transport, deterministic_jitter)
from .transport_socket import FrameChaos, FrameDecoder, SocketTransport
from .kv_cache import (HybridCache, KVCachePool, PoolExhaustedError,
                       PrefixMatch)
from .lora import (AdapterExhaustedError, AdapterPool,
                   AdapterUnavailableError, LoRAAdapter)
from .metrics import FleetMetrics, ServingMetrics, percentile
from .parallel import (TPContext, collective_counts, partition_devices,
                       validate_tp_config)
from .scheduler import (FINISHED, PREEMPTED, RUNNING, WAITING, Request,
                        SamplingParams, Scheduler)
from .snapshot import (RequestSnapshot, SnapshotStore,
                       load_engine_snapshot, save_engine_snapshot,
                       snapshot_from_wire, snapshot_to_wire)
from .speculative import DraftProposer, NgramDrafter, SpeculativeConfig
from .tiering import HostTier
from .workload import (Workload, WorkloadRequest, WorkloadSpec,
                       heavy_tail_workload, long_prompt_workload,
                       make_workload, overload_workload)

__all__ = [
    "ServingEngine", "BrownoutConfig",
    "KVCachePool", "PoolExhaustedError", "PrefixMatch", "HybridCache",
    "ServingMetrics", "FleetMetrics",
    "FleetRouter", "FleetRequest",
    "percentile", "Request", "SamplingParams", "Scheduler",
    "WAITING", "RUNNING", "PREEMPTED", "FINISHED",
    "SpeculativeConfig", "DraftProposer", "NgramDrafter",
    "HostTier",
    "AdapterPool", "LoRAAdapter",
    "AdapterExhaustedError", "AdapterUnavailableError",
    "SnapshotStore", "RequestSnapshot",
    "save_engine_snapshot", "load_engine_snapshot",
    "snapshot_to_wire", "snapshot_from_wire",
    "Workload", "WorkloadRequest", "WorkloadSpec", "heavy_tail_workload",
    "long_prompt_workload", "make_workload", "overload_workload",
    "ServingError", "QueueFullError", "RequestTooLargeError",
    "SchedulerStalledError", "EngineDrainingError", "FleetOverloadedError",
    "TPConfigError", "AdmissionShedError", "RecurrentStateError",
    "LatentCacheError",
    "TransportError", "StaleEpochError", "ReplicaSpawnError",
    "Transport", "LoopbackTransport", "ChaosTransport", "EngineServer",
    "Message", "deterministic_jitter",
    "SocketTransport", "FrameChaos", "FrameDecoder",
    "TPContext", "partition_devices", "validate_tp_config",
    "collective_counts",
]
