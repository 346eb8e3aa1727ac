"""Differential fuzz of the wide-composite path (DESIGN.md §3, §11):
LimbComposite digit encode/decode and the divisibility/factorize/gcd
kernels at every width against the exact Python-int oracle.

Every kernel assertion here is bit-exactness — the digit path must agree
with arbitrary-precision host arithmetic on every element, with zero
false positives (asserted by re-factorization, Theorem 1)."""

import math

import numpy as np
import pytest
from hypothesis_compat import given, settings, st
from strategies import (LimbUniverseSpec, build_limb_universe,
                        limb_universe_specs)

from repro.core.composite import (LIMB_BASE, LIMB_BITS, CompositeRegistry,
                                  LimbComposite, int_to_limbs, limbs_to_int,
                                  n_limbs_for_bits, pack_limbs, unpack_limbs)
from repro.kernels import divisibility_scan, factorize_batch, gcd_batch
from repro.kernels.ref import (divisibility_mask_ref,
                               factorize_squarefree_ref, gcd_squarefree_ref)

# widths covering one int64 word, a non-power-of-2 width, and deep chains
WIDTHS = (64, 96, 256, 1024)


# --------------------------------------------------------------------------- #
# encoding                                                                    #
# --------------------------------------------------------------------------- #

def test_limb_encoding_roundtrip_deterministic():
    vals = [0, 1, 2, LIMB_BASE - 1, LIMB_BASE, LIMB_BASE + 1,
            2**63 - 1, 2**63, 2**64, 2**200 + 12345, 2**1023]
    L = n_limbs_for_bits(1024)
    for v in vals:
        limbs = int_to_limbs(v, L)
        assert len(limbs) == L
        assert all(0 <= x < LIMB_BASE for x in limbs)
        assert limbs_to_int(limbs) == v
    arr = pack_limbs(vals, L)
    assert arr.shape == (len(vals), L) and arr.dtype == np.int32
    assert unpack_limbs(arr) == vals


def test_limb_composite_dataclass():
    c = LimbComposite.encode(2**100 + 7, n_limbs_for_bits(128))
    assert c.value == 2**100 + 7
    assert int(c) == 2**100 + 7
    assert c.n_limbs == 22                   # ceil(128 / 6) digits
    with pytest.raises(OverflowError):
        LimbComposite.encode(2**64, 2)       # needs 11 digits
    with pytest.raises(ValueError):
        int_to_limbs(-1, 4)


@given(st.integers(min_value=0, max_value=2**1024 - 1))
@settings(max_examples=200, deadline=None)
def test_limb_roundtrip_property(v):
    L = n_limbs_for_bits(max(1, v.bit_length()))
    assert limbs_to_int(int_to_limbs(v, L)) == v


# --------------------------------------------------------------------------- #
# kernels vs the Python-int oracle                                            #
# --------------------------------------------------------------------------- #

def _universe(seed, max_bits, **kw):
    spec = LimbUniverseSpec(seed=seed, max_bits=max_bits, **kw)
    return build_limb_universe(spec)


def _check_universe(pool, comps, max_bits):
    """One full differential pass: scan + factorize + gcd, kernel vs
    exact host arithmetic, plus the ref-oracle cross-check."""
    L = n_limbs_for_bits(max_bits)
    limbs = pack_limbs(comps, L)
    qs = pool[:: max(1, len(pool) // 64)]

    # §4.2 divisibility scan
    idx = divisibility_scan(limbs, qs)
    ref_mask = divisibility_mask_ref(limbs, np.asarray(qs))
    for j, q in enumerate(qs):
        want = [i for i, c in enumerate(comps) if c % q == 0]
        assert list(idx[j]) == want, (q, max_bits)
        assert list(np.nonzero(ref_mask[:, j])[0]) == want

    # Algorithm 2 factorize: mask + exact residual
    facs, residual = factorize_batch(limbs, pool)
    _, ref_res = factorize_squarefree_ref(limbs, np.asarray(pool))
    for c, fs, r, rr in zip(comps, facs, residual, unpack_limbs(ref_res)):
        rem = c
        for p in fs:
            assert rem % p == 0, "false positive factor (Theorem 1)"
            rem //= p
        assert r == rem == rr
        # re-factorization: the recovered factors reproduce the composite
        prod = 1
        for p in fs:
            prod *= p
        assert prod * r == c

    # pairwise gcd via pool reconstruction
    a = comps
    b = comps[1:] + comps[:1]
    gs = gcd_batch(a, b, pool)
    ref_gs = unpack_limbs(gcd_squarefree_ref(pack_limbs(a, L),
                                             pack_limbs(b, L)))
    for x, y, g, rg in zip(a, b, gs, ref_gs):
        assert g == math.gcd(x, y) == rg, (max_bits,)


@pytest.mark.parametrize("max_bits", WIDTHS)
def test_limb_kernels_match_oracle(max_bits):
    pool, comps = _universe(seed=max_bits, max_bits=max_bits)
    _check_universe(pool, comps, max_bits)


def test_limb_kernels_narrow_width_agrees_with_flat_path():
    """Values that fit int64 give the same answers whether they are
    packed at a narrow registry's digit count or a wide one's — the
    kernels have one representation for every width."""
    pool, comps = _universe(seed=3, max_bits=62, big_primes=False,
                            max_factors=3)
    assert max(comps) < 2**63
    narrow, wide = n_limbs_for_bits(62), n_limbs_for_bits(1024)
    facs_n, res_n = factorize_batch(pack_limbs(comps, narrow), pool)
    facs_w, res_w = factorize_batch(pack_limbs(comps, wide), pool)
    assert facs_n == facs_w and res_n == res_w
    b = comps[1:] + comps[:1]
    assert gcd_batch(pack_limbs(comps, narrow), pack_limbs(b, narrow),
                     pool) == \
        gcd_batch(pack_limbs(comps, wide), pack_limbs(b, wide), pool) == \
        [math.gcd(x, y) for x, y in zip(comps, b)]


def test_partial_pool_residual_is_exact():
    """A pool missing some member primes leaves the EXACT cofactor as
    residual — never a wrapped or truncated value."""
    known = [10007, 10009, 999_983]
    hidden = [1_000_003, 2**31 - 1]          # absent from the pool
    c = 1
    for p in known + hidden:
        c *= p
    facs, residual = factorize_batch([c], known)
    assert facs == [known]
    assert residual == [hidden[0] * hidden[1]]


@given(limb_universe_specs())
@settings(max_examples=25, deadline=None)
def test_limb_kernels_match_oracle_fuzz(spec):
    pool, comps = build_limb_universe(spec)
    _check_universe(pool, comps, spec.max_bits)


# --------------------------------------------------------------------------- #
# wide registry end to end                                                    #
# --------------------------------------------------------------------------- #

def test_wide_registry_scan_tables_match_host():
    """kernel successor tables over a wide registry == the host oracle's
    (the §4.2 scan routed through the digit kernels)."""
    from repro.core.assignment import PrimeAssigner
    from repro.core.engine.tables import successor_table
    from repro.core.primes import CacheLevel, HierarchicalPrimeAllocator

    reg = CompositeRegistry(max_bits=640)
    assigner = PrimeAssigner(HierarchicalPrimeAllocator(), reg)
    rng = np.random.default_rng(0)
    ids = list(range(60))
    for d in ids:
        assigner.assign(d, CacheLevel.MEM)   # primes >= 1e6: deep chains
    # one 19-deep group relationship (single wide chunk) + chain edges
    deep = [assigner.prime_of(d) for d in ids[:19]]
    reg.register(deep, kind="group")
    for a, b in zip(ids, ids[1:]):
        reg.register({assigner.prime_of(a), assigner.prime_of(b)},
                     kind="chain")
    host = successor_table(reg, assigner, ids, discover="host")
    kern = successor_table(reg, assigner, ids, discover="kernel")
    assert host == kern
    # sanity: the group relationship is one composite wider than int64
    assert any(c > 2**63 for c in reg.composites_list())
    with pytest.raises(OverflowError):
        reg.composites_array()


def test_wide_sharded_table_matches_host():
    """The collective exchange (wide digit rows) produces the same
    successor rows as the single-device host table at 2 and 4 shards."""
    from repro.core.assignment import PrimeAssigner
    from repro.core.engine.shard import (PrimeSpacePartition,
                                         sharded_successor_table)
    from repro.core.engine.tables import successor_table
    from repro.core.primes import CacheLevel, HierarchicalPrimeAllocator
    from repro.sharding.reshard import ShardSlices

    reg = CompositeRegistry(max_bits=640)
    assigner = PrimeAssigner(HierarchicalPrimeAllocator(), reg)
    ids = list(range(40))
    for d in ids:
        assigner.assign(d, CacheLevel.MEM)
    deep = [assigner.prime_of(d) for d in ids[:15]]
    reg.register(deep, kind="group")
    for a, b in zip(ids, ids[1:]):
        reg.register({assigner.prime_of(a), assigner.prime_of(b)},
                     kind="chain")
    host = successor_table(reg, assigner, ids, discover="host")
    for n_shards in (2, 4):
        split = ShardSlices(PrimeSpacePartition(n_shards))
        rows = sharded_successor_table(reg, assigner, ids, split, mesh=None)
        assert rows == host, f"{n_shards} shards"


def test_wide_serving_parity_all_backends():
    """kv="vec"|"sharded"|"elastic" at wide widths stay bit-exact with
    the narrow scalar oracle — chain placement is width-independent."""
    from repro.serving.engine import make_kv_backend

    def drive(kv, **kw):
        c = make_kv_backend(kv, hbm_pages=24, page_size=4,
                            prefetch_budget=4, **kw)
        rng = np.random.default_rng(1)
        for r in range(8):
            toks = [int(t) for t in
                    rng.integers(0, 40, size=rng.integers(8, 30))]
            if r % 2 == 0:
                toks[:8] = list(range(8))
            c.register_request(r, toks)
        items = []
        for _ in range(120):
            r = int(rng.integers(0, 8))
            n = len(c.chains.get(r, ()))
            if n:
                items.append((r, int(rng.integers(0, n))))
        tiers = c.touch_batch(items)
        return (c.stats.parity_tuple(), tiers, tuple(c.prefetch_log),
                c.shared_prefix(0, 2))

    base = drive("scalar")
    assert drive("scalar", max_bits=128) == base
    assert drive("vec", max_bits=128) == base
    assert drive("sharded", max_bits=128, mesh=None) == base
    assert drive("elastic", max_bits=1024, mesh=None) == base


def test_wide_tenancy_composes():
    from repro.serving.engine import make_kv_backend

    t = make_kv_backend("vec", hbm_pages=32, page_size=4,
                        prefetch_budget=4, tenants=2, max_bits=128)
    t.register_request(0, list(range(20)), tenant=0)
    t.register_request(1, list(range(20)), tenant=1)
    t.touch_batch([(0, 0), (1, 0), (0, 3), (1, 3)])
    assert t.cross_tenant_prefetches() == 0
    assert t.namespace.check_isolation(t.registry, pairwise_gcd=True).ok


def test_wide_shared_prefix_parity():
    """Scalar ``shared_prefix`` under a wide registry: deep chains whose
    chain composites exceed int64 (and any budgeted factorization) must
    still recover the exact shared page run — pool trial division over
    the chain's own primes is width-agnostic — and agree with the
    narrow scalar result and the vectorized batched-gcd twin."""
    from repro.serving.engine import make_kv_backend

    def drive(kv, max_bits):
        c = make_kv_backend(kv, hbm_pages=64, page_size=1,
                            prefetch_budget=0, max_bits=max_bits,
                            **({"mesh": None} if kv == "sharded" else {}))
        shared = list(range(40))                 # 40-page shared run
        c.register_request(0, shared + [100, 101])
        c.register_request(1, shared + [200])
        c.register_request(2, [300, 301, 302])   # disjoint control
        return c

    narrow = drive("scalar", 62)
    want = narrow.shared_prefix(0, 1)
    assert len(want) == 40                       # the whole shared run
    assert narrow.shared_prefix(0, 2) == []
    for kv in ("scalar", "vec", "sharded"):
        for max_bits in (128, 1024):
            c = drive(kv, max_bits)
            # the 40-prime chain composite genuinely exceeds int64
            comp = 1
            for pid in c.chains[0]:
                comp *= c.assigner.prime_of(pid)
            assert comp.bit_length() > 63
            assert c.shared_prefix(0, 1) == want, (kv, max_bits)
            assert c.shared_prefix(0, 2) == [], (kv, max_bits)
