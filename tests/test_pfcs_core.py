"""PFCS core: primes, factorization, composites — incl. the paper's
Theorem 1 (zero false positives) as a machine-checked property."""

import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import (CacheLevel, CompositeRegistry, Factorizer,
                        HierarchicalPrimeAllocator, PrimeAssigner,
                        encode_relationship, is_prime, segmented_sieve,
                        sieve_primes, spf_table)


# --------------------------------------------------------------------------- #
# primes                                                                      #
# --------------------------------------------------------------------------- #

def test_sieve_small():
    assert list(sieve_primes(30)) == [2, 3, 5, 7, 11, 13, 17, 19, 23, 29]


def test_sieve_counts():
    assert len(sieve_primes(1_000)) == 168
    assert len(sieve_primes(100_000)) == 9592


def test_spf_table_recovers_factorization():
    spf = spf_table(10_000)
    for n in [2, 4, 60, 97, 9991, 9999]:
        out = []
        m = n
        while m > 1:
            p = int(spf[m])
            out.append(p)
            m //= p
        prod = 1
        for p in out:
            prod *= p
        assert prod == n
        assert all(is_prime(p) for p in out)


def test_segmented_sieve_matches_full():
    full = sieve_primes(5_000)
    seg = segmented_sieve(1_000, 5_001)
    assert list(seg) == [int(p) for p in full if p >= 1_000]


@given(st.integers(min_value=2, max_value=10**6))
@settings(max_examples=200, deadline=None)
def test_is_prime_agrees_with_trial_division(n):
    ref = all(n % d for d in range(2, int(n**0.5) + 1))
    assert is_prime(n) == ref


def test_pool_allocation_ascending_and_recycle():
    alloc = HierarchicalPrimeAllocator()
    pool = alloc.pool(CacheLevel.L1)
    ps = [pool.allocate() for _ in range(10)]
    assert ps == sorted(ps) and ps[0] == 2
    pool.free(ps[3])
    assert pool.allocate() == ps[3]  # freed primes are reused first


def test_l1_pool_exhausts_at_168():
    alloc = HierarchicalPrimeAllocator()
    pool = alloc.pool(CacheLevel.L1)
    got = [pool.allocate() for _ in range(168)]
    assert all(p is not None for p in got)
    assert pool.allocate() is None  # bounded pool is dry


def test_mem_pool_is_unbounded():
    alloc = HierarchicalPrimeAllocator()
    pool = alloc.pool(CacheLevel.MEM)
    ps = [pool.allocate() for _ in range(5000)]
    assert all(p >= 1_000_003 for p in ps)
    assert len(set(ps)) == 5000


# --------------------------------------------------------------------------- #
# factorization (Algorithm 2)                                                 #
# --------------------------------------------------------------------------- #

def test_factorize_stages():
    f = Factorizer()
    assert f.factorize(143) == (11, 13)            # SPF table
    assert f.stats.table_hits == 1
    big = 1_000_003 * 1_000_033                    # Pollard rho territory
    assert f.factorize(big) == (1_000_003, 1_000_033)
    assert f.factorize(big) == (1_000_003, 1_000_033)  # cache hit
    assert f.stats.cache_hits >= 1


def test_factorize_with_multiplicity():
    f = Factorizer()
    assert f.factorize(8) == (2, 2, 2)
    assert f.factorize(2**3 * 3**2 * 97) == (2, 2, 2, 3, 3, 97)


@given(st.lists(st.sampled_from([2, 3, 5, 7, 11, 13, 1009, 99991,
                                 100_003, 999_983]),
                min_size=1, max_size=4, unique=True))
@settings(max_examples=100, deadline=None)
def test_factorize_roundtrip(primes):
    f = Factorizer()
    c = 1
    for p in primes:
        c *= p
    assert f.distinct_factors(c) == tuple(sorted(primes))


# --------------------------------------------------------------------------- #
# composites — Theorem 1                                                      #
# --------------------------------------------------------------------------- #

@given(st.sets(st.sampled_from(list(range(3, 600, 2))), min_size=2, max_size=8))
@settings(max_examples=150, deadline=None)
def test_zero_false_positives(odd_ids):
    """Theorem 1: decoding a relationship's composites recovers exactly the
    registered primes — never a superset, never a subset."""
    primes = sieve_primes(10_000)
    reg = CompositeRegistry()
    chosen = frozenset(int(primes[i]) for i in odd_ids)
    if len(chosen) < 2:
        return
    rel = reg.register(chosen)
    recovered = set()
    for c in rel.composites:
        recovered |= set(reg.decode(c))
    assert recovered == set(chosen)


def test_divisibility_scan_exact():
    reg = CompositeRegistry()
    r1 = reg.register({11, 13})
    r2 = reg.register({13, 17})
    r3 = reg.register({19, 23})
    hits = reg.containing(13)
    assert {r.rel_id for r in hits} == {r1.rel_id, r2.rel_id}
    assert reg.related_primes(13) == {11, 17}
    assert reg.related_primes(19) == {23}


def test_encode_relationship_chunks_overflow():
    big_primes = [1_000_003, 1_000_033, 1_000_037, 1_000_039,
                  1_000_081, 1_000_099, 1_000_117, 1_000_121]
    chunks = encode_relationship(big_primes, max_bits=62)
    assert len(chunks) > 1
    prod = 1
    for c in chunks:
        assert c < 2**62
        prod *= c
    expect = 1
    for p in big_primes:
        expect *= p
    assert prod == expect


def test_composite_overflow_is_detected_never_silent():
    """int64-overflow management (ROADMAP item 2): a deep relationship
    chain whose product wraps 2**63 must be chunked or rejected — never
    silently corrupted into a wrapped composite.

    Three layers of defense, each asserted:
      1. ``encode_relationship`` rejects any single prime that cannot
         fit the chunk budget at all;
      2. a deep chain registers as multiple exact chunks whose int64
         kernel view stays positive (no wraparound) and factorizes back
         to exactly the member primes (Theorem 1 survives the chunking);
      3. a registry misconfigured so chunks could exceed the signed
         int64 kernel word is rejected at construction.
    """
    # (1) an un-representable prime raises, both standalone and in a chain
    huge = (1 << 62) + 57                   # any value >= 2**62 works here
    with pytest.raises(ValueError):
        encode_relationship([huge], max_bits=62)
    with pytest.raises(ValueError):
        encode_relationship([11, huge], max_bits=62)

    # (2) deep chain: 40 primes near 2**20 -> product ~2**800, far past
    # int64; registration must stay exact via chunking
    reg = CompositeRegistry()
    primes = [p for p in range(1_048_583, 1_050_000) if is_prime(p)][:40]
    assert len(primes) == 40
    rel = reg.register(primes)
    assert len(rel.composites) > 1          # chunked, not wrapped
    arr = reg.composites_array()
    assert arr.dtype == np.int64
    assert (arr > 0).all()                  # a wrap would go negative
    prod = 1
    for c in rel.composites:
        assert 1 < c < 2**62
        prod *= c
    expect = 1
    for p in primes:
        expect *= p
    assert prod == expect                   # bit-exact over the chunks
    # factorization recovers the exact member set from the chunks
    members = set()
    for c in rel.composites:
        members |= set(reg.decode(int(c)))
    assert members == set(primes)
    # divisibility scan still finds the chain through any member
    assert reg.related_primes(primes[0]) == set(primes) - {primes[0]}

    # (3) degenerate chunk budgets are construction errors; widths past
    # one int64 word — which PR 6 rejected outright — now construct a
    # multi-limb wide registry ("represent, never raise", DESIGN.md §11)
    for bad in (1, 0, -5, 4097):
        with pytest.raises(ValueError):
            CompositeRegistry(max_bits=bad)
    assert CompositeRegistry(max_bits=63).max_bits == 63   # boundary ok
    for wide_bits in (64, 70, 1024):       # formerly ValueError traces
        wr = CompositeRegistry(max_bits=wide_bits)
        assert wr.wide
        rel_w = wr.register(primes)        # the same ~2**800 deep chain
        assert len(rel_w.composites) <= len(rel.composites)
        prod_w = 1
        for c in rel_w.composites:
            assert 1 < c < 2**wide_bits
            prod_w *= c
        assert prod_w == expect            # bit-exact at every width
        members_w = set()
        for c in rel_w.composites:
            # a wide chunk can be hundreds of bits — give the Pollard
            # tail a real budget instead of the 50ms per-access default
            members_w |= set(wr.factorizer.distinct_factors(
                int(c), time_budget_s=10.0))
        assert members_w == set(primes)
        with pytest.raises(OverflowError):
            wr.composites_array()          # int64 view refuses to wrap
    # a 1024-bit budget holds the whole chain in ONE exact chunk
    assert len(CompositeRegistry(max_bits=1024).register(primes)
               .composites) == 1


def test_encode_relationship_budget_boundary_edges():
    """ISSUE 8 satellite: the chunk boundary is inclusive on the value
    side, exclusive on the budget — a chunk product of exactly
    ``2**max_bits - 1`` is accepted, a member of exactly ``2**max_bits``
    is rejected with the existing message."""
    # 2**11 - 1 = 2047 = 23 * 89: the product lands EXACTLY on the
    # largest representable value and must stay one chunk
    assert encode_relationship([89, 23], max_bits=11) == [2047]
    # a Mersenne prime IS the largest representable value: accepted
    assert encode_relationship([8191], max_bits=13) == [8191]
    # one past the edge: 2**max_bits itself is rejected, with the same
    # message the PR 6 guard established
    with pytest.raises(ValueError,
                       match=r"exceeds 11-bit composite budget"):
        encode_relationship([2048], max_bits=11)
    with pytest.raises(ValueError,
                       match=r"exceeds 62-bit composite budget"):
        encode_relationship([1 << 62], max_bits=62)
    # product one past the edge splits instead of overflowing:
    # 3 * 683 = 2049 = 2**11 + 1
    assert encode_relationship([3, 683], max_bits=11) == [3, 683]


@given(st.lists(st.sampled_from([2, 3, 5, 7, 11, 13, 10007, 10009,
                                 1_000_003, 1_000_033]),
                min_size=1, max_size=12),
       st.randoms(use_true_random=False))
@settings(max_examples=150, deadline=None)
def test_encode_relationship_canonical_for_multisets(ms, rnd):
    """ISSUE 8 satellite: chunking is canonical in ONE place — the same
    multiset (duplicates included) produces the same chunk tuple
    regardless of caller order, at narrow and wide widths."""
    shuffled = list(ms)
    rnd.shuffle(shuffled)
    for mb in (62, 128):
        a = encode_relationship(ms, max_bits=mb)
        b = encode_relationship(shuffled, max_bits=mb)
        assert a == b
        prod = 1
        for c in a:
            prod *= c
        expect = 1
        for p in ms:
            expect *= p
        assert prod == expect              # duplicates all survive


def test_encode_relationship_canonical_deterministic():
    """Hypothesis-free pin of the canonical-chunking property (the
    tier-1 suite runs without dev deps): shuffled duplicate-prime
    multisets produce identical chunk tuples."""
    import random
    ms = [1_000_003, 2, 1_000_003, 999_983, 7, 7, 10007, 1_000_033,
          999_983, 3]
    rnd = random.Random(8)
    for mb in (62, 96, 1024):
        want = encode_relationship(ms, max_bits=mb)
        for _ in range(25):
            shuffled = list(ms)
            rnd.shuffle(shuffled)
            assert encode_relationship(shuffled, max_bits=mb) == want


def test_register_chunks_match_canonical_encoding():
    """``CompositeRegistry.register`` must not re-sort: its chunk tuple
    is exactly ``encode_relationship`` of the prime SET."""
    for mb in (62, 128):
        reg = CompositeRegistry(max_bits=mb)
        ps = {1_000_037, 11, 999_983, 10007}
        rel = reg.register(ps)
        assert list(rel.composites) == encode_relationship(ps, mb)


def test_drop_prime_purges_relationships():
    reg = CompositeRegistry()
    reg.register({11, 13})
    reg.register({11, 17})
    reg.register({19, 23})
    reg.drop_prime(11)
    assert len(reg) == 1
    assert reg.related_primes(13) == set()


def test_assigner_recycling_under_exhaustion():
    assigner = PrimeAssigner()
    # force many hot assignments into tiny L1 (168 primes)
    for i in range(200):
        assigner.tracker.record(i)
        assigner.tracker._freq[i] = 0.9  # hot -> L1-range selection
        p = assigner.assign(i, CacheLevel.L1)
        assert p is not None
    assert assigner.stats.recycle_events >= 1


# --------------------------------------------------------------------------- #
# prime-pool free / release audit (double-free + foreign-prime paths)         #
# --------------------------------------------------------------------------- #

def test_pool_double_free_is_noop():
    """A double-freed prime must NOT land on the free-list twice (two
    data elements sharing one prime would break unique decoding)."""
    alloc = HierarchicalPrimeAllocator()
    pool = alloc.pool(CacheLevel.L1)
    ps = [pool.allocate() for _ in range(4)]
    pool.free(ps[1])
    pool.free(ps[1])                    # double free: no-op
    assert pool.allocate() == ps[1]     # handed out once...
    nxt = pool.allocate()
    assert nxt != ps[1]                 # ...and only once
    assert pool.n_allocated == 5


def test_pool_foreign_and_unallocated_free_are_noops():
    alloc = HierarchicalPrimeAllocator()
    pool = alloc.pool(CacheLevel.L2)
    p = pool.allocate()
    before = (pool.n_allocated, len(pool._free))
    pool.free(5)           # foreign: out of the L2 value range entirely
    pool.free(1013)        # in range but never allocated here
    assert (pool.n_allocated, len(pool._free)) == before
    pool.free(p)
    assert pool.allocate() == p


def test_allocator_free_routes_to_owning_pool():
    """Freeing with a wrong level id used to leak the prime (the range
    guard made the mis-routed free a silent no-op, so the prime was
    never reusable); the allocator now routes by value ownership."""
    alloc = HierarchicalPrimeAllocator()
    p = alloc.allocate(CacheLevel.L2)
    alloc.free(CacheLevel.L1, p)        # wrong level on purpose
    assert alloc.allocate(CacheLevel.L2) == p   # reusable again
    # stats stay sane in the owning pool
    assert alloc.pool(CacheLevel.L1).n_allocated == 0


def test_assigner_release_idempotent_and_epoch():
    assigner = PrimeAssigner()
    p = assigner.assign("x", CacheLevel.L2)
    assert assigner.epoch == 0
    assigner.release("x", CacheLevel.L2)
    assert assigner.epoch == 1
    assert assigner.prime_of("x") is None
    assigner.release("x", CacheLevel.L2)        # double release: no-op
    assigner.release("never-seen", CacheLevel.L2)
    assert assigner.epoch == 1
    # the freed prime is reusable exactly once
    assert assigner.assign("y", CacheLevel.L2) == p
    assert assigner.assign("z", CacheLevel.L2) != p


# --------------------------------------------------------------------------- #
# batched (streamed) build — bit-identical to the per-element loop            #
# --------------------------------------------------------------------------- #

def _registry_state(reg):
    """Full observable registry state (dict orders included)."""
    return (
        reg._next_id, reg.version,
        list(reg._by_composite.items()),
        dict(reg._prime_degree),
        {rid: (r.rel_id, r.primes, r.composites, r.kind, r.weight)
         for rid, r in reg._by_id.items()},
    )


@pytest.mark.parametrize("max_bits", [62, 1024])
def test_batched_build_state_identity(max_bits):
    """``assign_many`` + ``register_many`` (the case_scale streamed
    build) must leave the assigner and registry in *bit-identical*
    state vs the scalar per-element loop — same primes in the same
    order, same relationship ids, same composite dict order, same
    ``version`` — in both narrow and wide (multi-limb) modes."""
    from repro.core.primes import CacheLevel as CL

    def build(batched):
        reg = CompositeRegistry(max_bits=max_bits)
        asg = PrimeAssigner(HierarchicalPrimeAllocator(), reg)
        n_chains, depth = 8, 12
        if batched:
            prime_of = asg.assign_many(range(n_chains * depth), CL.MEM)
        else:
            prime_of = [asg.assign(d, CL.MEM)
                        for d in range(n_chains * depth)]
        for c in range(n_chains):
            row = prime_of[c * depth:(c + 1) * depth]
            if batched:
                reg.register_many(zip(row, row[1:]), kind="chain")
            else:
                for a, b in zip(row, row[1:]):
                    reg.register((a, b), kind="chain")
            if c % 4 == 0:
                reg.register(row, kind="group")
        return reg, asg, prime_of

    r1, a1, p1 = build(False)
    r2, a2, p2 = build(True)
    assert p1 == p2
    assert _registry_state(r1) == _registry_state(r2)
    assert a1._data_to_prime == a2._data_to_prime
    assert a1._prime_to_data == a2._prime_to_data
    assert (a1.stats.assigned, a1.stats.reused) == \
           (a2.stats.assigned, a2.stats.reused)


def test_allocate_many_matches_scalar_sequence():
    from repro.core.primes import PrimePool

    s, b = PrimePool(level=0, lo=2, hi=997), PrimePool(level=0, lo=2, hi=997)
    assert [s.allocate() for _ in range(20)] == b.allocate_many(20)
    # free-list consumption is smallest-first in both paths
    for p in (7, 61, 13):
        s.free(p)
        b.free(p)
    assert [s.allocate() for _ in range(5)] == b.allocate_many(5)
    assert s._allocated == b._allocated
    assert sorted(s._free) == sorted(b._free)
    # bounded pool running dry: batched returns the scalar prefix
    sd, bd = PrimePool(level=0, lo=2, hi=29), PrimePool(level=0, lo=2, hi=29)
    scalar = [sd.allocate() for _ in range(20)]
    assert bd.allocate_many(20) == [p for p in scalar if p is not None]
    assert bd.allocate_many(3) == []
    assert bd.allocate_many(0) == []


def test_assign_many_mixed_warm_and_duplicates():
    """Warm elements and within-batch duplicates must break the bulk
    run and fall back to scalar ``assign`` at their original position,
    keeping allocation order (and stats) identical."""
    s = PrimeAssigner(registry=CompositeRegistry())
    b = PrimeAssigner(registry=CompositeRegistry())
    ds = ["a", "b", "c", "b", "d", "warm", "a", "e"]
    s.tracker.record("warm")
    b.tracker.record("warm")
    assert [s.assign(d, CacheLevel.L2) for d in ds] == \
        b.assign_many(ds, CacheLevel.L2)
    assert s._data_to_prime == b._data_to_prime
    assert (s.stats.assigned, s.stats.reused) == \
           (b.stats.assigned, b.stats.reused)


def test_register_many_error_parity_preserves_prefix():
    """A failing group mid-batch raises the canonical encoder error and
    leaves exactly the scalar loop's partial state (completed prefix
    registered, failing group not)."""
    groups = [(3, 5), (7, 11), (13, 1)]
    s, b = CompositeRegistry(), CompositeRegistry()
    with pytest.raises(ValueError, match="not a prime: 1") as e_scalar:
        for g in groups:
            s.register(g)
    with pytest.raises(ValueError, match="not a prime: 1") as e_batch:
        b.register_many(groups)
    assert str(e_scalar.value) == str(e_batch.value)
    assert _registry_state(s) == _registry_state(b)
    with pytest.raises(ValueError):
        b.register_many([(17,)])            # < 2 distinct elements
    # wide mode: oversized prime rejected with the canonical bound error
    w = CompositeRegistry(max_bits=128)
    with pytest.raises(ValueError, match="kernel prime bound"):
        w.register_many([(3, (1 << 31) + 11)])
