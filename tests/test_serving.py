"""Serving tier: PFCS paged KV cache, expert cache, engine end-to-end.

Parity discipline (mirrors tests/test_engine.py): the scalar
``PagedKVCache`` is the bit-exact oracle; ``VectorizedPagedKVCache``
must reproduce every ``PARITY_COUNTERS`` field, every per-touch tier,
and the exact HBM LRU order under any interleaving of registration and
touches — including HBM-slot exhaustion/eviction edges and the gcd
shared-prefix path.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from strategies import apply_kv_ops, drive_kv
from repro.serving.expert_cache import ExpertCache
from repro.serving.kv_cache import PARITY_COUNTERS, PagedKVCache
from repro.serving.kv_cache_sharded import ShardedPagedKVCache
from repro.serving.kv_cache_vec import VectorizedPagedKVCache

IMPLS = {
    "scalar": PagedKVCache,
    "vec": VectorizedPagedKVCache,
    "sharded": ShardedPagedKVCache,
}


def _mk(impl: str, **kw):
    return IMPLS[impl](**kw)


# --------------------------------------------------------------------------- #
# single-implementation behavior (both backends must satisfy it)              #
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("impl", list(IMPLS))
def test_prefix_sharing_is_content_addressed(impl):
    kv = _mk(impl, hbm_pages=64, page_size=4)
    a = kv.register_request(1, [1, 2, 3, 4, 5, 6, 7, 8])
    b = kv.register_request(2, [1, 2, 3, 4, 9, 9, 9, 9])
    assert a[0] == b[0]          # identical first block -> same page
    assert a[1] != b[1]


@pytest.mark.parametrize("impl", list(IMPLS))
def test_shared_prefix_via_gcd_exact(impl):
    kv = _mk(impl, hbm_pages=64, page_size=4)
    kv.register_request(1, list(range(16)))
    kv.register_request(2, list(range(8)) + [99, 98, 97, 96])
    shared = kv.shared_prefix(1, 2)
    # exactly the two pages covering tokens 0..7 — no false sharing
    assert len(shared) == 2
    kv.register_request(3, [55] * 16)
    assert kv.shared_prefix(1, 3) == []


@pytest.mark.parametrize("impl", list(IMPLS))
def test_page_prefetch_follows_chain(impl):
    kv = _mk(impl, hbm_pages=8, page_size=4, prefetch_budget=4)
    pages = kv.register_request(1, list(range(32)))   # 8-page chain
    kv.touch(1, 0)
    # successor of page 0 must now be HBM-resident (prefetched)
    assert pages[1] in kv.hbm
    assert kv.stats.prefetches >= 1


@pytest.mark.parametrize("impl", list(IMPLS))
def test_eviction_to_host_and_demand_return(impl):
    kv = _mk(impl, hbm_pages=2, page_size=4, prefetch_budget=0)
    kv.register_request(1, list(range(24)))           # 6 pages
    for i in range(6):
        kv.touch(1, i)
    assert len(kv.hbm) <= 2
    assert kv.stats.evictions > 0
    assert kv.stats.prefetches == 0                   # budget 0: disabled
    tier = kv.touch(1, 0)                             # long-evicted page
    assert tier == "host"


# --------------------------------------------------------------------------- #
# vec == scalar, bit for bit                                                  #
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("hbm,budget", [(16, 4), (2, 0), (64, 8), (4, 1),
                                        (1, 2)])
def test_vec_matches_scalar_oracle(hbm, budget):
    """Deterministic randomized workload (``strategies.drive_kv``):
    shared-prefix request mix, interleaved registration and touches,
    releases."""
    for seed in (0, 1, 2):
        a = PagedKVCache(hbm_pages=hbm, page_size=4, prefetch_budget=budget)
        b = VectorizedPagedKVCache(hbm_pages=hbm, page_size=4,
                                   prefetch_budget=budget)
        ta, tb = drive_kv(a, seed), drive_kv(b, seed)
        assert ta == tb                              # per-touch tiers
        for f in PARITY_COUNTERS:
            assert getattr(a.stats, f) == getattr(b.stats, f), f
        assert list(a.hbm.items()) == list(b.hbm.items())   # exact LRU order
        assert a.host == b.host
    # the scalar oracle scans the registry per touched page (when
    # prefetch is on); the vectorized cache must never scan on the
    # touch path
    if budget > 0:
        assert a.stats.registry_scans > 0
    assert b.stats.registry_scans == 0


def test_touch_batch_equals_sequential_touches():
    a = VectorizedPagedKVCache(hbm_pages=8, page_size=4, prefetch_budget=2)
    b = VectorizedPagedKVCache(hbm_pages=8, page_size=4, prefetch_budget=2)
    for kv in (a, b):
        kv.register_request(0, list(range(32)))
        kv.register_request(1, list(range(16)) + [77] * 16)
    items = [(0, 5), (1, 7), (0, 0), (1, 0), (0, 7), (0, 5)]
    bulk = a.touch_batch(items)
    seq = [b.touch(r, i) for r, i in items]
    assert bulk == seq
    assert a.stats.parity_tuple() == b.stats.parity_tuple()
    assert list(a.hbm.items()) == list(b.hbm.items())


def test_hbm_slot_exhaustion_single_slot():
    """Degenerate 1-slot HBM: every insert evicts, counters still match."""
    a = PagedKVCache(hbm_pages=1, page_size=4, prefetch_budget=3)
    b = VectorizedPagedKVCache(hbm_pages=1, page_size=4, prefetch_budget=3)
    for kv in (a, b):
        kv.register_request(0, list(range(40)))       # 10 pages
        for i in list(range(10)) + [0, 9, 5]:
            kv.touch(0, i)
    assert a.stats.parity_tuple() == b.stats.parity_tuple()
    assert list(a.hbm.items()) == list(b.hbm.items())
    assert a.stats.evictions > 0


def test_out_of_band_registry_drop_forces_rebuild():
    """An out-of-band registry mutation (Algorithm-1 prime recycling via
    ``assigner.release`` drops relationships) must not be masked by the
    incremental table maintenance: the next touch rebuilds in bulk and
    parity with the oracle holds.  The drop rides the chaos-event
    machinery (``strategies.apply_kv_ops`` schedule) so the same event
    stream also drives the elastic fuzz in tests/test_elastic.py."""
    ops = [("register", 0, tuple(range(16))),          # pages 0..3
           ("register", 1, tuple(list(range(8)) + [9] * 8)),
           ("touch", 0, 0), ("touch", 0, 2)]
    schedule = {1: [("drop", 1)]}                      # drop page 1's prime
    a = PagedKVCache(hbm_pages=8, page_size=4, prefetch_budget=2)
    b = VectorizedPagedKVCache(hbm_pages=8, page_size=4, prefetch_budget=2)
    tiers = {kv: apply_kv_ops(kv, ops, schedule=schedule) for kv in (a, b)}
    assert tiers[a] == tiers[b]
    assert a.stats.parity_tuple() == b.stats.parity_tuple()
    assert list(a.hbm.items()) == list(b.hbm.items())


def test_prime_pool_exhaustion_recycling_parity():
    """Drive BOTH caches into Algorithm-1 prime recycling (the
    ``recycle_fraction`` path) under long-horizon churn: tiny custom
    pools exhaust, hot upcoming pages take the recycle branch, recycled
    primes get reassigned — and the vectorized cache must stay bit-exact
    on PARITY_COUNTERS, per-touch tiers, LRU order, the prefetch log,
    AND gcd shared-prefix answers (the stale-chunk class of divergence
    regression-tested in tests/test_tenancy.py)."""
    from repro.core.assignment import PrimeAssigner
    from repro.core.primes import CacheLevel, HierarchicalPrimeAllocator

    ranges = {CacheLevel.L1: (2, 13), CacheLevel.L2: (17, 97),
              CacheLevel.L3: (101, 199), CacheLevel.MEM: (211, None)}

    def run(cls):
        kv = cls(hbm_pages=8, page_size=4, prefetch_budget=2)
        # shrink the prime space so churn actually exhausts it (no page
        # registered yet: identity state swaps cleanly)
        kv.assigner = PrimeAssigner(HierarchicalPrimeAllocator(ranges),
                                    kv.registry)
        tiers = []
        for r in range(40):
            # mark the upcoming pages hot (recycle needs freq > 0.3,
            # i.e. two EWMA records) — identical calls on both caches
            for k in range(6):
                kv.assigner.tracker.record(kv._next_page + k)
                kv.assigner.tracker.record(kv._next_page + k)
            kv.register_request(r, [r * 40 + k for k in range(16)])
            tiers.extend(kv.touch_batch(
                [(r, j) for j in range(len(kv.chains[r]))]))
            if r >= 8 and r % 3 == 0:
                kv.release_request(r - 8)
        return kv, tiers

    a, ta = run(PagedKVCache)
    b, tb = run(VectorizedPagedKVCache)
    # churn genuinely took the recycle path, identically
    assert a.assigner.stats.recycle_events > 0
    assert (a.assigner.stats.recycle_events
            == b.assigner.stats.recycle_events)
    assert (a.assigner.stats.recycled_primes
            == b.assigner.stats.recycled_primes)
    assert ta == tb
    for f in PARITY_COUNTERS:
        assert getattr(a.stats, f) == getattr(b.stats, f), f
    assert list(a.hbm.items()) == list(b.hbm.items())
    assert a.host == b.host
    assert a.prefetch_log == b.prefetch_log
    # gcd shared-prefix answers agree even with recycled+reused primes
    live = [r for r in a.chains if r in b.chains][-6:]
    for i in live:
        for j in live:
            if i < j:
                assert a.shared_prefix(i, j) == b.shared_prefix(i, j), (i, j)


def test_vec_rejects_bad_config():
    with pytest.raises(ValueError):
        VectorizedPagedKVCache(hbm_pages=0)
    with pytest.raises(ValueError):
        VectorizedPagedKVCache(discover="magic")


# --------------------------------------------------------------------------- #
# discovery tables: incremental == bulk host == bulk Pallas kernels           #
# --------------------------------------------------------------------------- #

def test_successor_table_backends_agree():
    from repro.core.engine import successor_table

    kv = VectorizedPagedKVCache(hbm_pages=16, page_size=4,
                                prefetch_budget=3)
    rng = np.random.default_rng(5)
    shared = list(rng.integers(0, 200, size=16))
    for r in range(8):
        tail = list(rng.integers(0, 200, size=int(rng.integers(4, 16))))
        kv.register_request(r, shared[:int(rng.integers(0, 16))] + tail)

    inc = kv.successor_rows()
    pages = range(kv._next_page)
    host = {k: v for k, v in successor_table(
        kv.registry, kv.assigner, pages, discover="host").items() if v}
    kern = {k: v for k, v in successor_table(
        kv.registry, kv.assigner, pages, discover="kernel").items() if v}
    assert inc == host == kern
    # a bulk kernel refresh reproduces the incrementally-maintained table
    kv.refresh_tables(discover="kernel")
    assert kv.successor_rows() == inc
    assert kv.bulk_refreshes == 1


def test_kernel_table_refuses_a_false_hit(monkeypatch):
    """Theorem 1: a scan hit on a composite the query prime does not
    divide trips the kernel path's check against the decoded factors."""
    from repro.core.engine import successor_table
    from repro.kernels import ops

    kv = VectorizedPagedKVCache(hbm_pages=16, page_size=4)
    kv.register_request(0, list(range(16)))        # edges (0,1) (1,2) (2,3)
    scan = ops.divisibility_scan

    def planted(digits, primes):
        hits = scan(digits, primes)
        # page 0's prime hits the last edge, which it is not part of
        hits[0] = np.union1d(hits[0], [digits.shape[0] - 1])
        return hits

    monkeypatch.setattr(ops, "divisibility_scan", planted)
    with pytest.raises(AssertionError,
                       match="divisibility hit must contain p"):
        successor_table(kv.registry, kv.assigner, range(4),
                        discover="kernel")


def test_shared_prefix_gcd_kernel_parity():
    """The vectorized cache recovers shared prefixes through the batched
    gcd kernel over chunked chain composites — identical to the scalar
    arbitrary-precision gcd."""
    a = PagedKVCache(hbm_pages=64, page_size=4)
    b = VectorizedPagedKVCache(hbm_pages=64, page_size=4)
    rng = np.random.default_rng(9)
    shared = list(rng.integers(0, 300, size=24))
    for kv in (a, b):
        rng2 = np.random.default_rng(9)
        for r in range(6):
            pfx = int(rng2.integers(0, 24))
            tail = list(rng2.integers(300, 600,
                                      size=int(rng2.integers(4, 30))))
            kv.register_request(r, shared[:pfx] + tail)
    pairs = [(i, j) for i in range(6) for j in range(i + 1, 6)]
    for i, j in pairs:
        assert a.shared_prefix(i, j) == b.shared_prefix(i, j), (i, j)
    # bulk path: every pair through ONE gcd_batch call
    bulk = b.shared_prefix_bulk(pairs)
    for p in pairs:
        assert bulk[p] == a.shared_prefix(*p), p


# --------------------------------------------------------------------------- #
# expert cache                                                                #
# --------------------------------------------------------------------------- #

def test_expert_cache_prefetch_beats_no_prefetch():
    """With structured co-activation, PFCS prefetch lifts the HBM hit rate
    vs an identical cache without relationship knowledge.  The workload
    comes from the shared expert-strategy builder (the same spec family
    the differential fuzz in tests/test_serving_moe.py draws from)."""
    from strategies import ExpertWorkloadSpec, build_expert_sets

    spec = ExpertWorkloadSpec(seed=0, n_experts=64, n_steps=150, batch=2,
                              group_size=8, n_groups=6)
    batches = build_expert_sets(spec)

    def run(prefetch_budget):
        ec = ExpertCache(spec.n_experts, hbm_slots=16,
                         prefetch_budget=prefetch_budget)
        for batch in batches:
            ec.observe_routing(batch)
            # activation arrives expert-by-expert (the all-to-all
            # schedule): head first, then the co-fired tail
            for g in batch:
                ec.activate([g[0]])
                ec.activate(list(g[1:]))
        return ec.stats.hit_rate

    assert run(prefetch_budget=7) > run(prefetch_budget=0)


# --------------------------------------------------------------------------- #
# serving engine                                                              #
# --------------------------------------------------------------------------- #

def test_engine_end_to_end_smoke():
    from repro.configs import get_smoke
    from repro.models import build_model
    from repro.serving.engine import ServingEngine

    cfg = get_smoke("qwen2.5-3b")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, max_batch=2, max_seq=96, page_size=8)
    shared = list(range(16))          # two full shared pages
    for i in range(3):
        eng.submit(shared + [20 + i], max_new_tokens=4)
    done = eng.run_until_idle()
    assert len(done) == 3
    assert all(len(r.generated) == 4 for r in done)
    assert eng.pages.stats.shared_prefix_pages > 0


def _engine_workload(eng, n_req=160, seed=0):
    rng = np.random.default_rng(seed)
    shared = list(rng.integers(0, 5000, size=48))
    for r in range(n_req):
        tail = list(rng.integers(0, 5000, size=int(rng.integers(8, 40))))
        eng.submit(shared[:int(rng.integers(0, 48))] + tail,
                   max_new_tokens=6)
    return eng.run_until_idle()


def test_engine_vec_scalar_parity():
    """Null-model engines over either cache backend produce identical
    tokens AND identical page counters on the same workload."""
    from repro.serving.engine import ServingEngine

    engines = {kv: ServingEngine(None, None, max_batch=16, page_size=8,
                                 hbm_pages=32, kv=kv, reread_window=2)
               for kv in ("vec", "scalar")}
    done = {kv: _engine_workload(e, n_req=48) for kv, e in engines.items()}
    gen = {kv: [(r.req_id, tuple(r.generated)) for r in sorted(
        ds, key=lambda r: r.req_id)] for kv, ds in done.items()}
    assert gen["vec"] == gen["scalar"]
    assert (engines["vec"].pages.stats.parity_tuple()
            == engines["scalar"].pages.stats.parity_tuple())
    assert engines["vec"].pages.stats.registry_scans == 0
    assert engines["scalar"].pages.stats.registry_scans > 0


def test_engine_sustains_hundred_plus_concurrency():
    """The vectorized cache lets one engine tick drive 100+ concurrent
    requests with zero per-page discovery scans (the load benchmark's
    acceptance gate, at test scale)."""
    from repro.serving.engine import ServingEngine

    eng = ServingEngine(None, None, max_batch=128, page_size=16,
                        hbm_pages=96, kv="vec", reread_window=2)
    done = _engine_workload(eng, n_req=192)
    assert len(done) == 192
    assert eng.peak_live >= 100
    assert eng.pages.stats.registry_scans == 0
    st = eng.pages.stats
    assert st.hbm_hits + st.host_hits + st.misses > 0
