"""Mesh-sharded paged KV cache: PFCS state partitioned across devices.

``VectorizedPagedKVCache`` (DESIGN.md §5) made the serving cache an
array state machine on ONE device.  This module partitions the cache's
*PFCS state* — the prime space, the chain-composite registry, and the
bulk-discovery work — across a ``("data", "model")`` device mesh
(DESIGN.md §6):

  * **Ownership.**  Every page's prime has exactly one owner shard
    (:class:`repro.core.engine.shard.PrimeSpacePartition` — contiguous
    prime-value blocks striped round-robin).  A chain edge whose two
    page primes share an owner lives in that shard's registry slice;
    an edge straddling prime ranges is cross-shard and rides the
    collective exchange.
  * **Per-shard bulk discovery.**  Successor tables are rebuilt
    per-shard through the existing Pallas divisibility kernel under
    ``shard_map`` (:func:`repro.core.engine.shard.
    sharded_successor_table`): each shard all-gathers the cross-shard
    composites (``lax.all_gather``, the collective exchange), then runs
    one divisibility scan of its local and gathered rows against the
    query primes it owns.  The assembled rows are bit-identical to the
    single-device table, so every placement decision — and therefore
    every ``PARITY_COUNTERS`` entry — stays bit-exact against the
    scalar oracle at ANY shard count (``tests/test_serving_sharded.py``).
  * **Owner-routed accounting.**  ``touch_batch`` routes each touch to
    the owner shard of the touched page: per-shard ``PageStats`` carry
    the same counters as the oracle's, and their field-wise sum equals
    the aggregate ``stats`` exactly — so existing parity checks apply
    unchanged to the sharded cache while per-shard load stays
    observable (``shard_load``).

Placement (HBM slot arrays, LRU stamps) deliberately remains ONE global
state machine: Theorem 1's zero-false-positive guarantee and the
oracle-parity contract both pin the *global* interleaving of demand and
prefetch traffic, and HBM is one physical resource per serving host.
What scales with the mesh is the discovery work — the §4.2 scans that
dominate registry-refresh cost — which drops to the per-shard slice
(see EXPERIMENTS.md, shard-scaling track).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from repro.core.engine.shard import (PrimeSpacePartition, ShardScanReport,
                                     shard_mesh, sharded_successor_table)
from repro.obs.trace import EV_GCD_EXCHANGE
from repro.sharding.reshard import ShardSlices

from .kv_cache import PARITY_COUNTERS, PageStats
from .kv_cache_vec import VectorizedPagedKVCache

__all__ = ["ShardedPagedKVCache"]


class ShardedPagedKVCache(VectorizedPagedKVCache):
    """Drop-in ``VectorizedPagedKVCache`` with mesh-partitioned PFCS
    state.  Tables are always maintained by per-shard bulk rebuild (the
    registry slices are the shards' source of truth; incremental
    append-maintenance is a single-device optimization), triggered at
    most once per ``touch_batch`` when the registry changed.
    """

    def __init__(self, hbm_pages: int = 1024, page_size: int = 16,
                 prefetch_budget: int = 4, n_shards: int = 2,
                 mesh="auto", stripes_per_shard: int = 8,
                 max_bits: int = 62):
        # discover="host" disables the incremental fast path, so every
        # registry change routes through the (sharded) bulk rebuild
        super().__init__(hbm_pages=hbm_pages, page_size=page_size,
                         prefetch_budget=prefetch_budget, discover="host",
                         max_bits=max_bits)
        self.partition = PrimeSpacePartition(n_shards, stripes_per_shard)
        self.n_shards = self.partition.n_shards
        # the registry split the sharded scan reads, kept current as the
        # registry grows
        self.slices = ShardSlices(self.partition)
        if mesh == "auto":
            mesh = shard_mesh(self.n_shards)
        if mesh is not None and mesh.size != self.n_shards:
            raise ValueError(f"mesh has {mesh.size} devices, cache has "
                             f"{self.n_shards} shards")
        self.mesh = mesh
        self.shard_stats: List[PageStats] = [PageStats()
                                             for _ in range(self.n_shards)]
        self.last_scan = ShardScanReport()

    # ------------------------------------------------------------------ #
    # ownership                                                           #
    # ------------------------------------------------------------------ #

    def owner_of_page(self, pid: int) -> int:
        """Owner shard of a page (pages without a prime fall to shard 0)."""
        p = self.assigner.prime_of(pid)
        return 0 if p is None else self.partition.owner(p)

    # ------------------------------------------------------------------ #
    # sharded bulk discovery                                              #
    # ------------------------------------------------------------------ #

    def _build_rows(self, discover: Optional[str]
                    ) -> Dict[int, List[int]]:
        """Every successor row by per-shard Pallas scans under
        ``shard_map``, cross-shard rows all-gathered.  An explicit
        ``discover="host"|"kernel"`` falls back to the single-device
        bulk path (cross-check hook for the parity tests)."""
        if discover is not None:
            return super()._build_rows(discover)
        self.last_scan = ShardScanReport()
        rows = sharded_successor_table(self.registry, self.assigner,
                                       range(self._next_page), self.slices,
                                       mesh=self.mesh, report=self.last_scan)
        if self.obs is not None:
            for sh, n_local in enumerate(self.last_scan.local_composites):
                self.obs.emit(EV_GCD_EXCHANGE, shard=sh, arg=n_local)
        self._ensure_pages(self._next_page)
        return rows

    # ------------------------------------------------------------------ #
    # owner-routed touches and per-shard accounting                       #
    # ------------------------------------------------------------------ #

    def _page_for_tokens(self, token_block) -> Tuple[int, bool]:
        before = self.stats.shared_prefix_pages
        pid, hit = super()._page_for_tokens(token_block)
        if self.stats.shared_prefix_pages > before:
            ss = self.shard_stats[self.owner_of_page(pid)]
            ss.shared_prefix_pages += 1
        return pid, hit

    def touch_batch(self, items: Sequence[Tuple[int, int]]) -> List[str]:
        """Demand-access a decode batch, routing each touch to the owner
        shard of its page.  Placement applies in submission order (the
        parity contract pins the global interleaving); what the routing
        decides is accounting — every counter delta a touch produces,
        including evictions and prefetches it triggers, is charged to
        the serving shard."""
        self._sync_tables()
        tiers: List[str] = []
        for r, i in items:
            pid = self.chains[r][i]
            ss = self.shard_stats[self.owner_of_page(pid)]
            before = self.stats.parity_tuple()
            tiers.append(self._touch_one(pid))
            for f, b, a in zip(PARITY_COUNTERS, before,
                               self.stats.parity_tuple()):
                if a != b:
                    setattr(ss, f, getattr(ss, f) + (a - b))
        return tiers

    # ------------------------------------------------------------------ #
    # aggregation / introspection                                         #
    # ------------------------------------------------------------------ #

    def aggregate_shard_stats(self) -> PageStats:
        """Field-wise sum of the per-shard stats — equals the global
        ``stats`` on every ``PARITY_COUNTERS`` entry (tested)."""
        agg = PageStats()
        for ss in self.shard_stats:
            for f in PARITY_COUNTERS:
                setattr(agg, f, getattr(agg, f) + getattr(ss, f))
        return agg

    def shard_load(self) -> List[Dict[str, int]]:
        """Per-shard counter snapshot for the load benchmark report."""
        return [{f: getattr(ss, f) for f in PARITY_COUNTERS}
                for ss in self.shard_stats]
