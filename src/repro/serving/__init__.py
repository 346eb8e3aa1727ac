"""Serving tier: PFCS page/expert management for the decode hot path.

The paper's technique as a first-class serving feature: KV pages and
MoE expert weights are data elements, chains and co-activation groups
are composites, and placement (HBM vs host) is driven by deterministic
factorization-based discovery — zero false-positive prefetch traffic
(Theorem 1), exactly where statistical prefetchers waste bandwidth.

Public entry points (documented with runnable examples in docs/api.md):

  * :class:`PagedKVCache`           — scalar paged-KV page manager (the
    bit-exact oracle; per-page §4.2 scans)
  * :class:`VectorizedPagedKVCache` — array-state page tables + bulk
    table-driven chain discovery (DESIGN.md §5, the serving hot path)
  * :class:`ShardedPagedKVCache`    — mesh-partitioned PFCS state:
    per-shard prime ranges, registry slices, and ``shard_map`` bulk
    discovery with a cross-shard all-gather exchange (DESIGN.md §6)
  * :class:`ServingEngine`          — continuous-batching engine over
    any of the caches; :meth:`ServingEngine.submit` /
    :meth:`ServingEngine.step` drive the request lifecycle
  * :class:`ExpertCache`            — scalar MoE expert-weight cache
    with co-activation prefetch (the bit-exact oracle; per-activation
    §4.2 scans)
  * :class:`VectorizedExpertCache`  — array expert residency + bulk
    table-driven co-fire discovery (DESIGN.md §7, the MoE serving hot
    path; ``ServingEngine`` takes it with ``moe="vec"``)
  * :class:`ElasticShardedPagedKVCache` — live resharding + shard-loss
    recovery by refactorization (DESIGN.md §9; ``ServingEngine`` takes
    it with ``kv="elastic"`` and exposes ``resize``/``fail_shard``)
  * :class:`SlotMachine`            — continuous-batching slot machine:
    prefill/decode disaggregation, open-loop async admission, chunked
    prefill, preempt/resume with factorization-recovered prefetch
    (DESIGN.md §10); :class:`SlotOracle` is its per-slot Python-loop
    twin, differentially fuzzed bit-exact
    (``tests/test_serving_batching.py``)

The vectorized and sharded caches must reproduce the oracle's
``PageStats`` / ``ExpertCacheStats`` counters bit-for-bit
(``tests/test_serving.py``, ``tests/test_serving_sharded.py``,
``tests/test_serving_moe.py``), mirroring the engine-vs-oracle
discipline of ``tests/test_engine.py``.
"""

from .dedup import (DEDUP_COUNTERS, DedupElasticShardedPagedKVCache,
                    DedupOracle, DedupShardedPagedKVCache,
                    DedupVectorizedPagedKVCache)
from .elastic import (ElasticController, ElasticShardedPagedKVCache,
                      RecoveryReport)
from .engine import Request, ServingEngine
from .expert_cache import (EXPERT_PARITY_COUNTERS, ExpertCache,
                           ExpertCacheStats)
from .expert_cache_vec import VectorizedExpertCache
from .kv_cache import PARITY_COUNTERS, PagedKVCache, PageStats
from .kv_cache_sharded import ShardedPagedKVCache
from .kv_cache_vec import VectorizedPagedKVCache
from .slots import (SlotMachine, SlotOracle, SlotRequest,
                    poisson_arrival_ticks)

__all__ = [
    "Request", "ServingEngine", "ExpertCache", "ExpertCacheStats",
    "EXPERT_PARITY_COUNTERS", "VectorizedExpertCache",
    "PagedKVCache", "PageStats", "PARITY_COUNTERS",
    "ShardedPagedKVCache", "VectorizedPagedKVCache",
    "ElasticShardedPagedKVCache", "ElasticController", "RecoveryReport",
    "SlotMachine", "SlotOracle", "SlotRequest", "poisson_arrival_ticks",
    "DEDUP_COUNTERS", "DedupOracle", "DedupVectorizedPagedKVCache",
    "DedupShardedPagedKVCache", "DedupElasticShardedPagedKVCache",
]
