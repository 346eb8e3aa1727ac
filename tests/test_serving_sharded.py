"""Mesh-sharded serving cache: differential parity + partition laws.

Discipline (extends tests/test_serving.py): the scalar ``PagedKVCache``
is the bit-exact oracle; ``VectorizedPagedKVCache`` AND
``ShardedPagedKVCache`` (at mesh sizes 1 and 2) must reproduce every
``PARITY_COUNTERS`` entry, every per-touch tier, and the exact HBM LRU
order under ANY interleaving of registration, touches, releases,
adversarial sweeps, and out-of-band registry drops — including the
1-slot-HBM eviction edge.  The same abstract op sequence
(``strategies.build_kv_ops``) replays against every implementation, so
a single drawn spec differentially exercises all four caches at once.
"""

import numpy as np
import pytest

from strategies import (ElasticEventSpec, KVWorkloadSpec, apply_kv_ops,
                        build_failure_schedule, build_kv_ops, given,
                        kv_workload_specs, settings, st)
from repro.core.engine.shard import (PrimeSpacePartition, shard_mesh,
                                     sharded_successor_table)
from repro.serving.kv_cache import PARITY_COUNTERS, PagedKVCache
from repro.serving.kv_cache_sharded import ShardedPagedKVCache
from repro.serving.kv_cache_vec import VectorizedPagedKVCache
from repro.sharding.reshard import ShardSlices


def _differential(spec: KVWorkloadSpec, hbm: int, budget: int,
                  espec: ElasticEventSpec = None) -> None:
    """Replay one spec against oracle / vec / sharded(1) / sharded(2).

    ``espec``, when given, injects workload-mutating chaos events (prime
    drops) through the same ``build_failure_schedule`` machinery the
    elastic fuzz uses (tests/test_elastic.py) — identical schedules
    replay against every implementation."""
    ops = build_kv_ops(spec)
    schedule = (build_failure_schedule(espec, len(ops))
                if espec is not None else None)
    caches = {
        "scalar": PagedKVCache(hbm_pages=hbm, page_size=4,
                               prefetch_budget=budget),
        "vec": VectorizedPagedKVCache(hbm_pages=hbm, page_size=4,
                                      prefetch_budget=budget),
        "shard1": ShardedPagedKVCache(hbm_pages=hbm, page_size=4,
                                      prefetch_budget=budget, n_shards=1),
        "shard2": ShardedPagedKVCache(hbm_pages=hbm, page_size=4,
                                      prefetch_budget=budget, n_shards=2),
    }
    tiers = {name: apply_kv_ops(kv, ops, schedule=schedule)
             for name, kv in caches.items()}
    oracle = caches["scalar"]
    for name, kv in caches.items():
        if name == "scalar":
            continue
        assert tiers[name] == tiers["scalar"], name
        for f in PARITY_COUNTERS:
            assert getattr(kv.stats, f) == getattr(oracle.stats, f), \
                (name, f)
        assert list(kv.hbm.items()) == list(oracle.hbm.items()), name
        assert kv.host == oracle.host, name
        assert kv.stats.registry_scans == 0, name
    for name in ("shard1", "shard2"):
        kv = caches[name]
        assert (kv.aggregate_shard_stats().parity_tuple()
                == kv.stats.parity_tuple()), name


# --------------------------------------------------------------------------- #
# property-based differential fuzz (hypothesis; clean SKIP without it)        #
# --------------------------------------------------------------------------- #

@given(spec=kv_workload_specs(),
       hbm=st.sampled_from([1, 2, 8, 32]),
       budget=st.integers(min_value=0, max_value=4))
@settings(max_examples=15, deadline=None)
def test_differential_fuzz_property(spec, hbm, budget):
    """Any drawn workload: all four caches agree bit-for-bit — tiers,
    parity counters, LRU order, host tier, per-shard aggregation."""
    _differential(spec, hbm, budget)


# deterministic pinned cases: the suite exercises the edge paths even
# when hypothesis is not installed (tier-1 must not lose this coverage)
_PINNED = [
    # 1-slot HBM: every insert evicts
    (KVWorkloadSpec(seed=3, n_requests=8, n_touches=80), 1, 3, None),
    # registry drop -> bulk table rebuild path, small HBM; the drops are
    # schedule-driven chaos events (strategies.build_failure_schedule)
    (KVWorkloadSpec(seed=5, n_requests=10, n_touches=100), 4, 2,
     ElasticEventSpec(seed=5, n_events=4, kill=False, resize=False,
                      drop=True)),
    # eviction-adversarial sweeps + releases, prefetch off
    (KVWorkloadSpec(seed=7, n_requests=12, n_touches=60, sweeps=2),
     8, 0, None),
    # deep shared prefixes, dense touches
    (KVWorkloadSpec(seed=11, n_requests=9, n_touches=120, key_space=60,
                    shared_pool=32, max_tail=6), 16, 4, None),
]


@pytest.mark.parametrize("spec,hbm,budget,espec", _PINNED,
                         ids=["hbm1", "registry-drop", "sweeps", "prefix"])
def test_differential_fuzz_pinned(spec, hbm, budget, espec):
    _differential(spec, hbm, budget, espec=espec)


# --------------------------------------------------------------------------- #
# prime-space partition laws                                                  #
# --------------------------------------------------------------------------- #

def test_partition_owner_is_total_stable_and_striped():
    part = PrimeSpacePartition(n_shards=4)
    primes = [2, 997, 1009, 1523, 6007, 99991, 100003, 999983, 1000003]
    owners = [part.owner(p) for p in primes]
    assert all(0 <= o < 4 for o in owners)
    assert owners == [part.owner(p) for p in primes]      # pure function
    assert list(part.owners(primes)) == owners
    # contiguity: within one value block, ownership never changes
    lo, width = part._blocks[1]                           # L2 level
    block0 = [p for p in range(lo, lo + width) if part.owner(p) is not None]
    assert len({part.owner(p) for p in block0}) == 1
    # striping: consecutive blocks rotate shards
    assert part.owner(lo) != part.owner(lo + width)
    # a real workload spreads ownership over >1 shard
    kv = ShardedPagedKVCache(hbm_pages=8, page_size=4, n_shards=4)
    kv.register_request(0, list(range(1024)))             # 256-page chain
    spread = {kv.owner_of_page(pid) for pid in kv.chains[0]}
    assert len(spread) > 1
    assert PrimeSpacePartition(1).owner(99991) == 0       # degenerate
    with pytest.raises(ValueError):
        PrimeSpacePartition(0)


def test_classify_partitions_registry_in_order():
    kv = ShardedPagedKVCache(hbm_pages=16, page_size=4, n_shards=2)
    rng = np.random.default_rng(2)
    shared = list(rng.integers(0, 3000, size=24))
    for r in range(8):
        # long tails -> several hundred pages -> chains straddle the
        # partition's prime blocks, so the cross-shard path is live
        tail = list(rng.integers(0, 3000, size=int(rng.integers(80, 200))))
        kv.register_request(r, shared[:int(rng.integers(0, 24))] + tail)
    kv.slices.sync(kv.registry)
    local, cross = kv.slices.local(), kv.slices.cross()
    arr = kv.registry.composites_view()
    all_pos = sorted(p for sh in local for p in sh) + sorted(cross)
    assert sorted(all_pos) == list(range(arr.size))       # exact partition
    assert cross == sorted(cross)

    def chunk_owners(pos):
        c = int(arr[pos])
        rel = kv.registry.relationship_of_composite(c)
        return {kv.partition.owner(q) for q in rel.primes if c % q == 0}

    for s, sh in enumerate(local):
        assert sh == sorted(sh)                           # registry order
        for pos in sh:
            assert chunk_owners(pos) == {s}
    for pos in cross:
        assert len(chunk_owners(pos)) > 1
    # at this scale chains straddle prime blocks: the exchange is live
    assert cross, "workload produced no cross-shard chains"


# --------------------------------------------------------------------------- #
# sharded bulk discovery == single-device bulk discovery                      #
# --------------------------------------------------------------------------- #

def _chain_registry():
    """Eight requests' chains over 28 pages sharing prefixes."""
    kv = VectorizedPagedKVCache(hbm_pages=16, page_size=4,
                                prefetch_budget=3)
    rng = np.random.default_rng(5)
    shared = list(rng.integers(0, 200, size=16))
    for r in range(8):
        tail = list(rng.integers(0, 200, size=int(rng.integers(4, 16))))
        kv.register_request(r, shared[:int(rng.integers(0, 16))] + tail)
    return kv, list(range(kv._next_page))


@pytest.mark.parametrize("n_shards,stripes,group", [
    (1, 8, False), (2, 8, False), (3, 8, False), (4, 8, False),
    # one-prime-wide blocks: most chain edges straddle owners
    (4, 100_000, False),
    # a group of MEM primes wider than one chunk whose chunks each have
    # one owner while the relationship has two
    (4, 8, True)], ids=["1", "2", "3", "4", "4-mostly-cross",
                        "4-multi-chunk-group"])
def test_sharded_successor_table_matches_global(n_shards, stripes, group):
    from repro.core.engine import successor_table
    from repro.core.primes import CacheLevel

    kv, pages = _chain_registry()
    part = PrimeSpacePartition(n_shards, stripes)
    if group:
        # three MEM primes in the first block (shard 0), three past the
        # block boundary (shard 1): 2**62 fits three, so two chunks
        mem = kv.assigner.allocator.pool(CacheLevel.MEM)
        lo, width = part._blocks[CacheLevel.MEM]
        ids = list(range(kv._next_page, kv._next_page + 6))
        for d in ids[:3]:
            kv.assigner.assign(d, CacheLevel.MEM)
        while mem.allocate() < lo + width:
            pass
        for d in ids[3:]:
            kv.assigner.assign(d, CacheLevel.MEM)
        members = [kv.assigner.prime_of(d) for d in ids]
        kv.registry.register(members, kind="group")
        group_pos = [i for i, c in
                     enumerate(kv.registry.composites_view().tolist())
                     if kv.registry.relationship_of_composite(c).kind
                     == "group"]
        assert len(group_pos) == 2
        assert len({part.owner(q) for q in members}) == 2
        pages = range(ids[-1] + 1)
    host = successor_table(kv.registry, kv.assigner, pages, discover="host")
    sl = ShardSlices(part)
    sl.sync(kv.registry)
    local, cross = sl.local(), sl.cross()
    if group:
        # each chunk is local to its own shard: the split is by chunk
        assert group_pos[0] in local[0] and group_pos[1] in local[1]
    if stripes > 8:
        assert len(cross) > sum(map(len, local))
    # shard_map where the host has the devices, else the host loop
    sharded = sharded_successor_table(kv.registry, kv.assigner, pages,
                                      sl, mesh=shard_mesh(n_shards))
    assert sharded == host


def _hub_registry():
    """One hub page chained to more pages than the slots a padded query
    block keeps (``HITS_PER_ROW`` x 512): the hub's prime overflows its
    shard's hit vector."""
    from repro.core.primes import CacheLevel
    from repro.kernels.factorize import HITS_PER_ROW

    kv = VectorizedPagedKVCache(hbm_pages=16, page_size=4)
    ids = list(range(HITS_PER_ROW * 512 + 8))
    for d in ids:
        kv.assigner.assign(d, CacheLevel.L3)
    hub = kv.assigner.prime_of(0)
    for d in ids[1:]:
        kv.registry.register([hub, kv.assigner.prime_of(d)], kind="chain")
    # the hub and a few spokes, so every shard has queries
    return kv, ids[:64]


@pytest.mark.parametrize("case,n_shards,stripes", [
    ("chains", 1, 8), ("chains", 2, 1000), ("chains", 4, 1000),
    ("no-local-rows", 4, 2000), ("no-queries", 4, 1000),
    ("cross-heavy", 4, 10_000), ("overflow", 2, 100_000)])
def test_compacting_exchange_matches_global(monkeypatch, case, n_shards,
                                            stripes):
    """The host-loop exchange, each shard's hits compacted by the same
    program the mesh runs, gives the single-device table's rows; a
    shard copies its dense mask (``dense``) only when its hits overflow
    their slots."""
    from repro.core.engine import successor_table
    from repro.core.engine.shard import ShardScanReport
    from repro.kernels import ops
    from repro.obs import profile

    kv, ids = _hub_registry() if case == "overflow" else _chain_registry()
    part = PrimeSpacePartition(n_shards, stripes)
    owner = [part.owner(kv.assigner.prime_of(d)) for d in ids]
    if case == "no-queries":
        ids = [d for d, s in zip(ids, owner) if s != 3]
    sl = ShardSlices(part)
    scan_hits, dense = ops._scan_hits, []

    def recorded(*args):
        out = scan_hits(*args)
        dense.append(out[2])
        return out

    monkeypatch.setattr(ops, "_scan_hits", recorded)
    report = ShardScanReport()
    with profile.recording() as kept:
        sharded = sharded_successor_table(kv.registry, kv.assigner, ids, sl,
                                          mesh=None, report=report)
    assert sharded == successor_table(kv.registry, kv.assigner, ids,
                                      discover="host")
    local, queries = report.local_composites, report.queries_per_shard
    if case == "no-local-rows":
        assert any(n == 0 < q for n, q in zip(local, queries))
    elif case == "no-queries":
        assert local[3] > 0 == queries[3]
    elif case == "cross-heavy":
        assert report.cross_composites > 4 * sum(local)
    elif case == "overflow":
        assert all(queries)             # the other shard scans as well
    hub_shard = owner[0] if case == "overflow" else None
    assert dense == [s == hub_shard for s in range(n_shards)]
    ex = next(r for r in kept if r.name == "kernels.sharded_exchange")
    assert ex.counts["dense"] == sum(dense)


def test_sharded_refresh_crosschecks_against_kernel_backend():
    kv = ShardedPagedKVCache(hbm_pages=16, page_size=4,
                             prefetch_budget=3, n_shards=2)
    rng = np.random.default_rng(6)
    for r in range(6):
        kv.register_request(r, list(rng.integers(0, 150,
                                                 size=int(rng.integers(8, 20)))))
    kv.refresh_tables()                       # sharded path
    sharded_rows = kv.successor_rows()
    kv.refresh_tables(discover="kernel")      # single-device Pallas bulk
    assert kv.successor_rows() == sharded_rows


# --------------------------------------------------------------------------- #
# mesh plumbing                                                               #
# --------------------------------------------------------------------------- #

def test_degenerate_single_device_mesh_uses_shard_map():
    """n_shards=1 always has enough devices: the real shard_map path
    must run (and stay bit-exact) even on a 1-device host."""
    kv = ShardedPagedKVCache(hbm_pages=8, page_size=4, n_shards=1)
    assert kv.mesh is not None and kv.mesh.size == 1
    oracle = PagedKVCache(hbm_pages=8, page_size=4)
    for c in (kv, oracle):
        c.register_request(0, list(range(32)))
        c.touch_batch([(0, j) for j in range(8)])
    assert kv.last_scan.used_shard_map
    assert kv.stats.parity_tuple() == oracle.stats.parity_tuple()


def test_multi_device_mesh_when_forced():
    """Under XLA_FLAGS=--xla_force_host_platform_device_count=2 (the CI
    mesh job) the 2-shard cache runs real shard_map + all_gather; on a
    1-device host it falls back to the bit-identical host loop."""
    import jax

    n_dev = len(jax.devices())
    mesh = shard_mesh(2)
    assert (mesh is None) == (n_dev < 2)
    kv = ShardedPagedKVCache(hbm_pages=8, page_size=4, n_shards=2)
    kv.register_request(0, list(range(64)))
    kv.touch_batch([(0, j) for j in range(16)])
    assert kv.last_scan.used_shard_map == (n_dev >= 2)
    assert kv.bulk_refreshes >= 1


def test_mesh_shard_mismatch_rejected():
    mesh = shard_mesh(1)
    with pytest.raises(ValueError):
        ShardedPagedKVCache(n_shards=2, mesh=mesh)


# --------------------------------------------------------------------------- #
# serving engine over the sharded backend                                     #
# --------------------------------------------------------------------------- #

def test_engine_sharded_scalar_parity():
    """Null-model engines over the sharded vs scalar cache produce
    identical tokens AND identical page counters (mirrors
    test_serving.py::test_engine_vec_scalar_parity)."""
    from repro.serving.engine import ServingEngine

    def workload(eng, n_req=24, seed=0):
        rng = np.random.default_rng(seed)
        shared = list(rng.integers(0, 3000, size=48))
        for r in range(n_req):
            tail = list(rng.integers(0, 3000, size=int(rng.integers(8, 32))))
            eng.submit(shared[:int(rng.integers(0, 48))] + tail,
                       max_new_tokens=4)
        return eng.run_until_idle()

    engines = {kv: ServingEngine(None, None, max_batch=8, page_size=8,
                                 hbm_pages=24, kv=kv, reread_window=2,
                                 shards=2)
               for kv in ("sharded", "scalar")}
    done = {kv: workload(e) for kv, e in engines.items()}
    gen = {kv: [(r.req_id, tuple(r.generated)) for r in sorted(
        ds, key=lambda r: r.req_id)] for kv, ds in done.items()}
    assert gen["sharded"] == gen["scalar"]
    assert (engines["sharded"].pages.stats.parity_tuple()
            == engines["scalar"].pages.stats.parity_tuple())
    assert engines["sharded"].pages.stats.registry_scans == 0
    assert engines["sharded"].pages.bulk_refreshes >= 1


def test_engine_rejects_unknown_kv_backend():
    from repro.serving.engine import ServingEngine

    with pytest.raises(ValueError):
        ServingEngine(None, None, kv="magic")
