"""Pallas kernels vs exact oracles: shape/width sweeps, exact equality
(integer kernels — no tolerance).

Every kernel takes int32 lanes (DESIGN.md §3).  The ``np.int32`` cases
use composites below 2**31; the ``np.int64`` cases use composites above
2**31 (products of primes just under the 25-bit kernel prime bound),
which the kernels see as the same 6-bit digit rows as any other width.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.composite import n_limbs_for_bits, pack_limbs, unpack_limbs
from repro.core.primes import (MAX_PRIME_LIMIT, PrimePool, segmented_sieve)
from repro.kernels.factorize import (HITS_PER_ROW, divisibility_hits_pallas,
                                     divisibility_mask_pallas,
                                     factorize_hits_pallas,
                                     factorize_squarefree_pallas)
from repro.kernels.gcd import gcd_squarefree_pallas
from repro.kernels.ops import divisibility_scan, factorize_batch, gcd_batch
from repro.kernels.ref import (divisibility_mask_ref,
                               factorize_squarefree_ref, gcd_squarefree_ref)

PRIMES_SMALL = np.array(
    [2, 3, 5, 7, 11, 13, 17, 19, 23, 29, 31, 37, 41, 43, 47, 53, 59, 61,
     67, 71, 73, 79, 83, 89, 97, 101, 103, 107, 109, 113], dtype=np.int64)

#: the 30 widest primes the kernels take: pairwise products are ~2**50
PRIMES_WIDE = segmented_sieve(MAX_PRIME_LIMIT - 2_000, MAX_PRIME_LIMIT)[-30:]

DIGITS = n_limbs_for_bits(62)           # a narrow registry row


def _pad(x, mult, fill):
    pad = (-len(x)) % mult
    return np.concatenate([x, np.full(pad, fill, x.dtype)])


def _primes(dtype):
    return PRIMES_SMALL if dtype == np.int32 else PRIMES_WIDE


def _pairs(rng, dtype, n, replace=True):
    """``n`` products of two primes; ``replace=False`` keeps them
    squarefree (distinct primes), which the pool gcd needs."""
    ps = _primes(dtype)
    comps = [int(a) * int(b) for a, b in
             (rng.choice(ps, size=2, replace=replace) for _ in range(n))]
    assert (max(comps) < 2**31) == (dtype == np.int32)
    return comps


@pytest.mark.parametrize("n,p,bn,bp", [
    (256, 512, 256, 512),
    (512, 512, 256, 512),
    (256, 1024, 128, 256),
    (1024, 512, 512, 128),
])
@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_factorize_kernel_matches_ref(n, p, bn, bp, dtype):
    rng = np.random.default_rng(n + p)
    pool = _pad(_primes(dtype).astype(np.int32), bp, 0)[:p]
    digits = pack_limbs(_pairs(rng, dtype, n), DIGITS)
    mask, res = factorize_squarefree_pallas(
        jnp.asarray(digits), jnp.asarray(pool), block_n=bn, block_p=bp,
        interpret=True)
    mref, rref = factorize_squarefree_ref(digits, pool)
    assert (np.asarray(mask) == mref).all()
    assert (np.asarray(res) == rref).all()


def _edge_case(name):
    """``(values, pool, bits, block_n, block_p)`` for one case of the
    division rounds: how many dividing primes a lane has in a tile, and
    where."""
    small = [int(q) for q in PRIMES_SMALL]
    wide = [int(q) for q in PRIMES_WIDE]
    first15 = math.prod(small[:15])                 # 2 * 3 * ... * 47
    if name == "many_primes_narrow":
        vals = [first15, 6, first15 // 2, 1] + [1] * 252
        return vals, _pad(PRIMES_SMALL.astype(np.int32), 512, 0), 62, 256, 512
    if name == "many_primes_wide":
        vals = [math.prod(wide), math.prod(wide[::2]), wide[0] * wide[-1],
                math.prod(wide[1:]) * 7] + [1] * 252
        return vals, _pad(PRIMES_WIDE.astype(np.int32), 512, 0), 1024, \
            256, 512
    if name == "squared_factor":
        vals = [4 * 3, 9 * 5 * 7, 2**5 * 3, 47**2 * 43, 113**2,
                wide[0]**2 * 5] + [1] * 250
        pool = np.concatenate([PRIMES_SMALL, [wide[0]]]).astype(np.int32)
        return vals, _pad(pool, 512, 0), 62, 256, 512
    if name == "no_hits":
        # the second composite tile and the second prime tile hit nothing
        vals = ([127 * 131, 6, 1, 137 * 139 * 149] + [1] * 252
                + [127 * 131 * 151] * 256)
        pool = np.concatenate([PRIMES_SMALL.astype(np.int32),
                               np.zeros(128 - 30, np.int32),
                               np.asarray([157, 163, 167], np.int32),
                               np.zeros(128 - 3, np.int32)])
        return vals, pool, 62, 256, 128
    if name == "hits_across_prime_tiles":
        # primes 17 lanes apart, zeros between: four prime tiles of 128
        pool = np.zeros(512, np.int32)
        pool[:30 * 17:17] = PRIMES_SMALL
        vals = [first15, math.prod(small[::7]), 2 * 113, 61 * 107] \
            + [math.prod(small[k:k + 8]) for k in range(0, 22)] + [1] * 230
        return vals, pool, 62, 128, 128
    if name == "pad_rows_and_zero_primes":
        vals = [6, 35, 0, 1, 143] + [1] * 251
        pool = np.zeros(512, np.int32)
        pool[[3, 40, 300, 511]] = [2, 5, 7, 11]
        return vals, pool, 62, 256, 512
    if name == "repeated_pool_prime":
        # selected by row, not by value: a repeated prime divides twice
        vals = [9 * 5, 3 * 7, 5, 27] + [1] * 252
        return vals, _pad(np.asarray([3, 5, 3, 7], np.int32), 512, 0), \
            62, 256, 512
    raise KeyError(name)


@pytest.mark.parametrize("case", [
    "many_primes_narrow", "many_primes_wide", "squared_factor", "no_hits",
    "hits_across_prime_tiles", "pad_rows_and_zero_primes",
    "repeated_pool_prime"])
def test_factorize_kernel_edge_cases_match_ref(case):
    vals, pool, bits, bn, bp = _edge_case(case)
    digits = pack_limbs(vals, n_limbs_for_bits(bits))
    mask, res = factorize_squarefree_pallas(
        jnp.asarray(digits), jnp.asarray(pool), block_n=bn, block_p=bp,
        interpret=True)
    mref, rref = factorize_squarefree_ref(digits, pool)
    assert (np.asarray(mask) == mref).all()
    assert (np.asarray(res) == rref).all()


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_divmask_kernel_matches_ref(dtype):
    rng = np.random.default_rng(0)
    comps = _pairs(rng, dtype, 300) + [1] * (512 - 300)
    digits = pack_limbs(comps, DIGITS)
    qs = _pad(_primes(dtype).astype(np.int32), 512, 0)
    mask = divisibility_mask_pallas(jnp.asarray(digits), jnp.asarray(qs),
                                    interpret=True)
    assert (np.asarray(mask) == divisibility_mask_ref(digits, qs)).all()


@pytest.mark.parametrize("n", [1024, 2048, 4096])
@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_gcd_kernel_matches_ref(n, dtype):
    """Squarefree products below (int32) and above (int64) 2**31, gcd
    rebuilt from the prime pool."""
    rng = np.random.default_rng(n)
    a = pack_limbs(_pairs(rng, dtype, n, replace=False), DIGITS)
    b = pack_limbs(_pairs(rng, dtype, n, replace=False), DIGITS)
    pool = _pad(_primes(dtype).astype(np.int32), 512, 0)
    g = gcd_squarefree_pallas(jnp.asarray(a), jnp.asarray(b),
                              jnp.asarray(pool), interpret=True)
    assert (np.asarray(g) == gcd_squarefree_ref(a, b)).all()


def test_gcd_zero_edge():
    """gcd(0, x) = x (every pool prime divides 0), pad rows of 1 give 1,
    and zero-padded pool primes divide nothing."""
    va = [0, 6, 0, 1, 35] + [1] * 251
    vb = [6, 0, 1, 35, 1] + [1] * 251
    pool = _pad(PRIMES_SMALL.astype(np.int32), 512, 0)
    g = gcd_squarefree_pallas(jnp.asarray(pack_limbs(va, DIGITS)),
                              jnp.asarray(pack_limbs(vb, DIGITS)),
                              jnp.asarray(pool), interpret=True)
    assert unpack_limbs(np.asarray(g)) == \
        [math.gcd(x, y) for x, y in zip(va, vb)]


# --------------------------------------------------------------------------- #
# host wrappers (digit packing, range checks, padding, compaction)            #
# --------------------------------------------------------------------------- #

def test_factorize_batch_ragged():
    facs, resid = factorize_batch([6, 35, 143, 101], [2, 3, 5, 7, 11, 13])
    assert facs == [[2, 3], [5, 7], [11, 13], []]
    assert list(resid) == [1, 1, 1, 101]


def test_factorize_batch_int64_path():
    big = 1_000_003 * 1_000_033
    facs, resid = factorize_batch([big], [1_000_003, 1_000_033])
    assert facs[0] == [1_000_003, 1_000_033] and resid[0] == 1


def test_divisibility_scan_compaction():
    idx = divisibility_scan([6, 10, 15, 21], [2, 3, 5, 7])
    assert [list(i) for i in idx] == [[0, 1], [0, 2, 3], [1, 2], [3]]


def _wrapper_case(name):
    """``(values, query primes, pool, bits)`` for the compacting
    wrappers."""
    small = [int(q) for q in PRIMES_SMALL]
    wide = [int(q) for q in PRIMES_WIDE]
    if name == "narrow":
        vals = _pairs(np.random.default_rng(3), np.int32, 300)
        # 127 divides nothing: a query with no hits, among the rest
        return vals, [127] + small, small + [127], 62
    if name == "wide":
        vals = _pairs(np.random.default_rng(4), np.int64, 300) + [
            math.prod(wide[:4])]
        return vals, [wide[0] + 2] + wide, wide, 1024
    if name == "empty_registry":
        return [], small, small, 62
    if name == "mostly_padding":
        # whole 128-lane blocks of pad rows and 500 pad query rows
        return [6, 35, 1], [7, 3], [2, 3, 5, 7], 62
    if name == "over_the_cap":
        # 15 dividing primes a composite and 3,842 hits in a scan of 512
        # padded queries, past HITS_PER_ROW: the dense fallback
        first15 = math.prod(small[:15])
        return [first15] * 256 + [6], small, small, 62
    raise KeyError(name)


@pytest.mark.parametrize("case", ["narrow", "wide", "empty_registry",
                                  "mostly_padding", "over_the_cap"])
def test_compacted_wrappers_match_dense_ref(case):
    """Hit coordinates compacted on the device give exactly the oracle's
    per-query rows and per-composite factors and residuals, the dense
    fallback past the cap included."""
    vals, queries, pool, bits = _wrapper_case(case)
    digits = pack_limbs(vals, n_limbs_for_bits(bits))
    if case == "over_the_cap":
        assert 15 > HITS_PER_ROW and len(vals) * 15 > HITS_PER_ROW * 512
    idx = divisibility_scan(digits, queries)
    mask = divisibility_mask_ref(digits, queries)
    assert len(idx) == len(queries)
    for j, got in enumerate(idx):
        assert got.dtype == np.int64
        assert got.tolist() == np.nonzero(mask[:, j])[0].tolist()
    facs, resid = factorize_batch(digits, pool)
    fmask, fres = factorize_squarefree_ref(digits, np.asarray(pool))
    assert facs == [[pool[j] for j in np.nonzero(row)[0]] for row in fmask]
    assert resid == unpack_limbs(fres)


def test_compacting_entries_compile_once_per_padded_shape():
    """The number of hits sizes nothing: wrapper calls of one padded
    shape, the first with no hits (as the benchmark's warm-up makes
    them) and the next with many, share one executable."""
    ones = np.zeros((256, DIGITS), np.int32)
    ones[:, 0] = 1
    hits = pack_limbs(_pairs(np.random.default_rng(5), np.int32, 256),
                      DIGITS)
    pool = [int(q) for q in PRIMES_SMALL]
    entries = (divisibility_hits_pallas, factorize_hits_pallas)
    totals, sizes = [], []
    for d in (ones, hits):
        idx = divisibility_scan(d, pool)
        facs, _ = factorize_batch(d, pool)
        totals.append((sum(map(len, idx)), sum(map(len, facs))))
        sizes.append([f._cache_size() for f in entries])
    assert totals[0] == (0, 0) and min(totals[1]) > 0
    assert sizes[0] == sizes[1]


def test_scan_empty_inputs():
    out = divisibility_scan([], [3])
    assert len(out) == 1 and len(out[0]) == 0
    assert gcd_batch([], [], [2, 3]) == []


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_gcd_batch_matches_math_gcd(dtype):
    """The host wrapper over squarefree pairs below and above 2**31,
    sides of unequal digit width included, equals ``math.gcd``."""
    rng = np.random.default_rng(7)
    a = _pairs(rng, dtype, 200, replace=False)
    b = _pairs(rng, dtype, 199, replace=False) + [1]
    want = [math.gcd(x, y) for x, y in zip(a, b)]
    pool = [int(p) for p in _primes(dtype)]
    assert gcd_batch(a, b, pool) == want
    assert gcd_batch(pack_limbs(a, DIGITS), b, pool) == want


def test_kernel_prime_bound_is_enforced():
    """Primes at or past 2**25 are refused where they enter the kernels
    and where a pool would mint them — never truncated."""
    with pytest.raises(ValueError, match="25-bit"):
        divisibility_scan([6], [MAX_PRIME_LIMIT + 15])
    with pytest.raises(ValueError, match="25-bit"):
        factorize_batch([6], [2, MAX_PRIME_LIMIT + 15])
    pool = PrimePool(level=3, lo=MAX_PRIME_LIMIT - 100, hi=None,
                     initial_capacity=8)
    minted = []
    with pytest.raises(ValueError, match="25-bit"):
        for _ in range(64):
            minted.append(pool.allocate())
    assert minted and max(minted) < MAX_PRIME_LIMIT


def test_kernels_refuse_x64():
    with jax.enable_x64(True):
        with pytest.raises(RuntimeError, match="32-bit"):
            divisibility_scan([6, 10], [2, 3])
