"""On-device scoring service: micro-batched, state-cached, candidate→rank fused.

The production-serving analog of the reference's OpenVINO-compiled-model +
ANN-index stack (SURVEY §2.8), built from this repo's own pieces:

* :class:`MicroBatcher` — fills fixed ``[B, L]`` slots from concurrent
  requests under a max-wait deadline, with bounded per-lane queues and a
  supervised worker (``batcher``).
* :class:`UserStateCache` — per-user encoded-state LRU with one-step
  incremental window advances (``cache``).
* :class:`ScoringEngine` — pre-compiled ``CompiledInference`` bucket
  executables per length bucket + cached-state scorers (``engine``).
* :class:`CandidatePipeline` — exact sharded MIPS retrieval fused with the
  two-stage re-rank and top-k, all on device (``pipeline``).
* :class:`CircuitBreaker` — closed→open→half-open supervision of the encode
  path (``breaker``), and :class:`FallbackScorer` — the host-side popularity
  floor of the degradation ladder (``degrade``).
* :class:`ScoringService` — the end-to-end service (``service``), with
  admission control (:class:`RequestShed`), per-request deadlines
  (:class:`DeadlineExceeded`) and graceful degradation (``served_by`` tags).
* :class:`ParamStore` / :class:`PromotionController` — zero-downtime weight
  swaps and SLO-guarded canary promotion (``promote``): versioned parameter
  generations hot-swap into the running executables without recompiling,
  behind a shadow→canary→promoted|rolled_back state machine (docs/robustness
  "Zero-downtime swaps and canary promotion").
* :class:`ServingFleet` / :class:`HashRing` — N replicas behind a host-side
  consistent-hash router (``fleet``/``router``): bounded-movement user →
  replica mapping so state caches stay hot, per-replica health states
  (healthy → degraded → draining → dead) driven by heartbeats + exporter
  gauges, failover with the rerouted users riding the degradation ladder,
  p99-hedged requests, retry backoff honoring ``retry_after_s``, and a
  drain-and-swap rollout composing with the promotion path (docs/serving.md
  "The fleet").

``tests/serve/`` drives it in process — concurrent clients, an open-loop burst
above capacity, injected engine faults, hot swaps under load; the benchmark has
no serving cell yet (``PERF.md`` §7). See docs/serving.md.

Attach an :class:`~replay_tpu.obs.QualityMonitor` via ``ScoringService(
quality=...)`` to watch the MODEL-quality plane of the same traffic (online
prequential hitrate/NDCG, coverage/novelty/surprisal, PSI drift — docs/
observability.md "The quality plane"); :func:`top_k_cut` is the shared
ranked-cut contract over both :class:`ScoreResponse` shapes it relies on.
"""

from .batcher import MicroBatcher
from .breaker import CircuitBreaker
from .cache import UserState, UserStateCache
from .degrade import DEGRADATION_LADDER, FallbackScorer
from .engine import ScoringEngine
from .errors import (
    CircuitOpen,
    DeadlineExceeded,
    NoHealthyReplica,
    RequestShed,
    ServeError,
    ServiceClosed,
)
from .fleet import ReplicaHandle, ServingFleet
from .pipeline import CandidatePipeline
from .promote import (
    PROMOTION_STAGES,
    ParamGeneration,
    ParamStore,
    PromotionController,
    in_canary_slice,
)
from .quant import QuantizedTable, quantization_error, quantize_embeddings
from .remote import RemoteReplica, ReplicaServer, ReplicaServerProcess
from .request import ScoreRequest, ScoreResponse, make_window, top_k_cut
from .router import REPLICA_HEALTH, BackoffPolicy, HashRing, ReplicaHealth
from .service import ScoringService

__all__ = [
    "DEGRADATION_LADDER",
    "PROMOTION_STAGES",
    "REPLICA_HEALTH",
    "BackoffPolicy",
    "CandidatePipeline",
    "CircuitBreaker",
    "CircuitOpen",
    "DeadlineExceeded",
    "FallbackScorer",
    "HashRing",
    "MicroBatcher",
    "NoHealthyReplica",
    "ParamGeneration",
    "ParamStore",
    "PromotionController",
    "RemoteReplica",
    "ReplicaHandle",
    "ReplicaHealth",
    "ReplicaServer",
    "ReplicaServerProcess",
    "RequestShed",
    "ScoreRequest",
    "ScoreResponse",
    "ScoringEngine",
    "ScoringService",
    "ServeError",
    "ServiceClosed",
    "ServingFleet",
    "UserState",
    "QuantizedTable",
    "UserStateCache",
    "in_canary_slice",
    "make_window",
    "quantization_error",
    "quantize_embeddings",
    "top_k_cut",
]
