"""paddle_tpu.serving — continuous-batching LLM serving over the paged KV
cache (ROADMAP north star: "serves heavy traffic from millions of users").

- :mod:`.engine` — :class:`ServingEngine`: iteration-level (Orca-style)
  scheduler over a fixed-shape decode batch; one compiled step per
  iteration, donated page pools, per-slot positions.
- :mod:`.block_manager` — :class:`BlockManager`: vLLM-style paged KV block
  allocation, capacity-based admission control, optional prefix sharing.
- :mod:`.prefix_index` — :class:`RadixPrefixIndex`: page-granular radix
  tree over prompt ids (``ServingEngine(prefix_cache="radix")``) — partial
  prefix matches reuse the longest shared page run and prefill starts
  past the cached tokens (README "Hierarchical KV cache").
- :mod:`.kv_spill` — :class:`KVSpillTier`: host-DRAM middle tier
  (``kv_spill=True``): idle pages evicted by the radix index spill to
  host buffers under ``PADDLE_KV_SPILL_BUDGET_BYTES`` and resurrect into
  free device slots on the next prefix hit, accounted by the MemoryLedger
  as ``kv.spilled``.
- :mod:`.adapter` — model adapters (:class:`GPTAdapter`) reducing a causal
  LM to the prefill/step closures the engine compiles.
- :mod:`.api` — :class:`ContinuousBatchingPredictor`, the
  ``paddle.inference``-shaped deployment facade.
- :mod:`.speculative` — :class:`NgramDrafter` (prompt-lookup drafts) +
  :func:`make_verifier` (multi-token acceptance / rejection sampling) for
  ``ServingEngine(speculative_k=k)`` draft-and-verify decoding.
- :mod:`.cluster` — multi-replica serving: :class:`ReplicaPool` (N engines
  over one model), :class:`PrefixAffinityRouter` (rendezvous prefix
  routing with health-aware least-loaded fallback) and
  :class:`ServingCluster` (the routed facade with cross-replica in-flight
  requeue; README "Cluster serving").
- :mod:`.quant` — quantized serving: int8 paged KV pools with parallel
  scale pools (:class:`QuantizedGPTAdapter`, ``ServingEngine(kv_dtype=
  "int8")``), the :func:`quantize_model_weights` Int8Linear weight path,
  and the :func:`calibrate` accuracy harness (README "Quantized
  serving").
- :mod:`.qos` — QoS-tiered serving: :class:`TierPolicy` /
  :class:`QoSConfig` (priority tiers with weighted admission, per-tier
  SLOs, brownout shed thresholds), :class:`TieredQueue` (the engine's
  per-tier weighted-round-robin queue), :func:`brownout` (the shed
  ladder) and :class:`AutoScaler` (elastic replica count for a
  :class:`ReplicaPool` — README "QoS tiers & autoscaling").
- :mod:`.multitenant` — multi-tenant serving: paged multi-LoRA
  (:class:`LoRAStore` rank-bucketed adapter pools with per-row gather
  inside the compiled programs), grammar-constrained decoding
  (:func:`compile_json_schema` / :func:`compile_regex` token FSMs masking
  the batched sampler), and embed/score request modes — all batched by
  ONE :class:`MultiTenantEngine` (README "Multi-tenant serving").

Metrics (PR-1 registry, README "Serving"): ``serving.*`` histograms /
gauges / counters — TTFT, inter-token latency, queue depth, slot
occupancy, page-pool utilization, admission/preemption/trace counters,
speculative proposal/acceptance, prefix-cache hit/miss/eviction/saved
tokens, KV-spill pages/resurrections/drops/bytes.
"""

from .adapter import GPTAdapter, StatedCacheAdapter  # noqa: F401
from .api import ContinuousBatchingPredictor  # noqa: F401
from .block_manager import BlockManager, PageAllocation  # noqa: F401
from .prefix_index import RadixPrefixIndex, prefix_digest  # noqa: F401
from .kv_spill import KVSpillTier  # noqa: F401
from .engine import (  # noqa: F401
    EngineStoppedError, Request, RequestHandle, RequestRejectedError,
    SamplingParams, ServingEngine,
)
from ..observability.slo import SLOPolicy  # noqa: F401  (engine/cluster slo=)
from .speculative import NgramDrafter, make_verifier  # noqa: F401
from .cluster import (  # noqa: F401
    ClusterHandle, PrefixAffinityRouter, ReplicaPool, RouteDecision,
    ServingCluster,
)
from .quant import (  # noqa: F401
    QuantizedGPTAdapter, calibrate, quantize_model_weights,
)
from .multitenant import (  # noqa: F401
    CompiledGrammar, LoRAAdapter, LoRAStore, MultiTenantEngine,
    compile_json_schema, compile_regex,
)
from .qos import (  # noqa: F401
    AutoScaler, QoSConfig, TieredQueue, TierPolicy, brownout,
)

__all__ = [
    "ServingEngine", "Request", "RequestHandle", "RequestRejectedError",
    "EngineStoppedError", "SamplingParams", "BlockManager", "PageAllocation",
    "RadixPrefixIndex", "KVSpillTier", "prefix_digest",
    "GPTAdapter", "StatedCacheAdapter", "ContinuousBatchingPredictor", "NgramDrafter",
    "make_verifier", "ServingCluster", "ClusterHandle", "ReplicaPool",
    "PrefixAffinityRouter", "RouteDecision", "SLOPolicy",
    "QuantizedGPTAdapter", "quantize_model_weights", "calibrate",
    "MultiTenantEngine", "LoRAStore", "LoRAAdapter", "CompiledGrammar",
    "compile_regex", "compile_json_schema",
    "QoSConfig", "TierPolicy", "TieredQueue", "AutoScaler", "brownout",
]
