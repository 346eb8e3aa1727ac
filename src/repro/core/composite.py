"""Composite relationship encoding & registry (PFCS §3.1, §4.2).

A relationship over data elements {d1..dk} with primes {p1..pk} is stored
as the composite c = Π pi.  The Fundamental Theorem of Arithmetic makes the
decoding (factorization) unique — Theorem 1's zero-false-positive
guarantee, which the test-suite checks as a machine property.

64-bit overflow management
--------------------------
The paper implicitly assumes composites fit machine words ("systems with
10**12 elements require primes within 64-bit ranges", §7.1).  Products of
many primes overflow regardless, so the registry *chunks* a k-ary
relationship into composites that each fit ``max_bits`` (default 62, so
every chunk also fits the host's int64 view); all chunks share a
relationship id.  Pairwise relationships — the dominant case in the
paper's workloads (FK pairs, feature pairs, instrument pairs) — always
fit.

Device digits (DESIGN.md §3, §11)
---------------------------------
The device kernels see every composite, narrow or wide, as
``ceil(max_bits / LIMB_BITS)`` little-endian ``LIMB_BITS``-bit digits,
one digit per int32 lane.  Member primes must stay below
``MAX_PRIME_LIMIT`` (2**25, enforced where the pools mint primes) so that
every kernel step — ``remainder * 2**LIMB_BITS + digit`` in the
divisibility ladder and the short division, ``digit * p + carry`` in the
gcd rebuild — stays below 2**31: exact int32 arithmetic, no 64-bit lanes,
no float paths.  ``max_bits > 63`` switches the registry to wide mode:
chunks may exceed int64 on the host too, so a single chunk can hold a
100+-deep chain composite without overflow ("represent, never raise").
Theorem 1's zero-false-positive guarantee is untouched because chunk
values are the same products of distinct primes, merely re-encoded.

The registry also maintains the int64 view of live composites
(narrow mode, host-side arithmetic) and the ``(N, n_limbs)`` int32 digit
matrix the kernels consume (``limbs_array``), in both modes.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, FrozenSet, Iterable, List, Optional, Sequence, Set, Tuple

import numpy as np

from .factorization import Factorizer
from .primes import MAX_PRIME_BITS, MAX_PRIME_LIMIT

__all__ = ["encode_relationship", "CompositeRegistry", "Relationship",
           "LimbComposite", "LIMB_BITS", "LIMB_BASE", "MAX_PRIME_BITS",
           "MAX_COMPOSITE_BITS", "n_limbs_for_bits", "int_to_limbs",
           "limbs_to_int", "pack_limbs", "unpack_limbs"]

#: device digit width: one ``LIMB_BITS``-bit digit per int32 lane.
#: ``LIMB_BITS + MAX_PRIME_BITS == 31`` keeps every kernel step below
#: 2**31 (see the module docstring) — no wraparound, no 64-bit lanes.
LIMB_BITS = 31 - MAX_PRIME_BITS
LIMB_BASE = 1 << LIMB_BITS
LIMB_MASK = LIMB_BASE - 1

#: sanity cap on chunk width (683 digits) — wide enough for 150+-deep
#: chains of MEM-level primes in ONE chunk, small enough that a
#: misconfigured budget cannot allocate absurd digit matrices.
MAX_COMPOSITE_BITS = 4096


def n_limbs_for_bits(max_bits: int) -> int:
    """Limbs needed to hold any value < 2**max_bits."""
    return -(-int(max_bits) // LIMB_BITS)


def int_to_limbs(x: int, n_limbs: int) -> List[int]:
    """Little-endian ``LIMB_BITS``-bit digits of a non-negative int."""
    return [int(d) for d in pack_limbs([x], n_limbs)[0]]


def limbs_to_int(limbs: Sequence[int]) -> int:
    """Inverse of :func:`int_to_limbs` (exact Python int)."""
    return unpack_limbs(np.asarray([list(limbs)]))[0]


def pack_limbs(values: Sequence[int], n_limbs: int) -> np.ndarray:
    """Pack non-negative ints into the ``(N, n_limbs)`` int32 digit
    matrix the kernels take (bytes -> bits -> digits, vectorized)."""
    vals = [int(v) for v in values]
    nbits = n_limbs * LIMB_BITS
    for v in vals:
        if v < 0:
            raise ValueError(f"composites are positive, got {v}")
        if v.bit_length() > nbits:
            raise OverflowError(
                f"value needs more than {n_limbs} limbs ({nbits} bits)")
    nbytes = -(-nbits // 8)
    raw = np.frombuffer(b"".join(v.to_bytes(nbytes, "little") for v in vals),
                        dtype=np.uint8).reshape(len(vals), nbytes)
    bits = np.unpackbits(raw, axis=1, bitorder="little")[:, :nbits]
    weights = (1 << np.arange(LIMB_BITS, dtype=np.int32))
    return (bits.reshape(len(vals), n_limbs, LIMB_BITS).astype(np.int32)
            * weights).sum(axis=2, dtype=np.int32)


def unpack_limbs(arr: np.ndarray) -> List[int]:
    """Exact Python ints back out of an ``(N, L)`` digit matrix."""
    digits = np.asarray(arr, dtype=np.int64)
    n, L = digits.shape
    bits = ((digits[:, :, None] >> np.arange(LIMB_BITS)) & 1).astype(np.uint8)
    raw = np.packbits(bits.reshape(n, L * LIMB_BITS), axis=1,
                      bitorder="little")
    return [int.from_bytes(row.tobytes(), "little") for row in raw]


@dataclass(frozen=True)
class LimbComposite:
    """One composite as fixed-width little-endian ``LIMB_BITS``-bit digits.

    The scalar unit of the wide registry encoding: ``encode`` splits an
    exact Python-int chunk value into limbs, ``value`` reassembles it
    bit-exactly.  The registry's ``limbs_array()`` is the batched (N, L)
    form of this for the Pallas kernels.
    """

    limbs: Tuple[int, ...]

    @classmethod
    def encode(cls, value: int, n_limbs: int) -> "LimbComposite":
        return cls(tuple(int_to_limbs(value, n_limbs)))

    @property
    def value(self) -> int:
        return limbs_to_int(self.limbs)

    def __int__(self) -> int:
        return self.value

    @property
    def n_limbs(self) -> int:
        return len(self.limbs)


def encode_relationship(primes: Sequence[int], max_bits: int = 62) -> List[int]:
    """Chunk a multiset of primes into composites, each < 2**max_bits.

    This is the ONE canonical chunking point: the input multiset is
    sorted here (and only here), so the same multiset produces the same
    chunk tuple regardless of caller order — including duplicate-prime
    multisets, where ``sorted`` keeps every occurrence.  Callers must NOT
    pre-sort (``CompositeRegistry.register`` passes its frozenset
    straight through).

    Greedy first-fit keeps chunk count minimal for sorted input.  The
    boundary is inclusive on the value side and exclusive on the budget:
    a chunk product of exactly ``2**max_bits - 1`` is accepted, a prime
    of exactly ``2**max_bits`` is rejected.  Raises if any single prime
    alone exceeds the bound (cannot be represented), or reaches the
    ``MAX_PRIME_BITS``-bit kernel prime bound (the pools refuse to mint
    such primes; see DESIGN.md §3).
    """
    limit = 1 << max_bits
    chunks: List[int] = []
    cur = 1
    for p in sorted(primes):
        if p <= 1:
            raise ValueError(f"not a prime: {p}")
        if p >= limit:
            raise ValueError(f"prime {p} exceeds {max_bits}-bit composite budget")
        if p >= MAX_PRIME_LIMIT:
            raise ValueError(
                f"prime {p} exceeds the {MAX_PRIME_BITS}-bit kernel prime "
                f"bound (digit arithmetic would overflow int32)")
        if cur * p >= limit:
            chunks.append(cur)
            cur = p
        else:
            cur *= p
    if cur > 1:
        chunks.append(cur)
    return chunks


@dataclass(frozen=True)
class Relationship:
    """One registered relationship (e.g. an FK edge or co-access group)."""

    rel_id: int
    primes: FrozenSet[int]
    composites: Tuple[int, ...]
    kind: str = "generic"
    weight: float = 1.0


class CompositeRegistry:
    """Live store of relationship composites with divisibility scanning.

    API mirrors the paper's use:
      * ``register(primes)``       — establish a relationship (composite(s))
      * ``related_to(p)``          — §4.2 intelligent prefetch: all primes
                                     co-occurring with p in any composite,
                                     recovered *by factorization*.
      * ``composites_array()``     — int64 host view (narrow mode).
      * ``limbs_array()``          — int32 digit matrix for the kernels.
    """

    def __init__(self, factorizer: Optional[Factorizer] = None, max_bits: int = 62):
        if not 1 < max_bits <= MAX_COMPOSITE_BITS:
            # max_bits <= 63 keeps every chunk inside one signed int64
            # host word (the flat composites_array() view); anything
            # wider flips the registry into wide mode, where only exact
            # Python ints and the digit matrix hold chunks, and the cap
            # only guards against absurd digit matrices.
            raise ValueError(
                f"max_bits must be in (1, {MAX_COMPOSITE_BITS}], "
                f"got {max_bits}")
        self.factorizer = factorizer or Factorizer()
        self.max_bits = max_bits
        #: wide mode: chunks may exceed int64 — consumers must use the
        #: digit matrix (limbs_array) or exact Python ints
        #: (composites_list / composites_view), never composites_array.
        self.wide = max_bits > 63
        #: digits per kernel row: enough for any value < 2**max_bits
        #: (the kernels take this matrix in both modes)
        self.n_limbs = n_limbs_for_bits(max_bits)
        self._next_id = 0
        self._by_id: Dict[int, Relationship] = {}
        self._by_composite: Dict[int, int] = {}  # composite -> rel_id
        self._prime_degree: Dict[int, int] = {}  # prime -> #relationships
        self._dirty = True
        self._arr: np.ndarray = np.empty(0, dtype=np.int64)
        self._limbs: np.ndarray = np.empty((0, self.n_limbs), dtype=np.int32)
        self._limbs_version = -1
        self.version = 0  # bumped on every mutation (memoization key)

    # -- registration -------------------------------------------------------

    def register(self, primes: Iterable[int], kind: str = "generic", weight: float = 1.0) -> Relationship:
        pset = frozenset(int(p) for p in primes)
        if len(pset) < 2:
            raise ValueError("a relationship needs >= 2 distinct elements")
        # canonical chunking happens INSIDE encode_relationship (the one
        # sort) — passing the frozenset unsorted is deliberate.
        comps = tuple(encode_relationship(pset, self.max_bits))
        rel = Relationship(self._next_id, pset, comps, kind, weight)
        self._next_id += 1
        self._by_id[rel.rel_id] = rel
        for c in comps:
            self._by_composite[c] = rel.rel_id
        for p in pset:
            self._prime_degree[p] = self._prime_degree.get(p, 0) + 1
        self._dirty = True
        self.version += 1
        return rel

    def register_many(self, groups: Iterable[Iterable[int]],
                      kind: str = "generic",
                      weight: float = 1.0) -> List[Relationship]:
        """Batched :meth:`register`, bit-identical to the per-element loop.

        Same validation, same canonical chunking, same id sequence, and
        the same final ``version`` (bumped once per registration, so
        version-keyed memoizers observe the same epoch).  The speedup
        comes from hoisting the dict attribute lookups out of the hot
        loop and deferring the ``_next_id`` / ``version`` writebacks —
        the streamed-build path for million-composite registries
        (``benchmarks.cases.case_scale``).  If a group fails validation
        mid-batch, the completed prefix stays registered exactly as the
        scalar loop would leave it.
        """
        by_id = self._by_id
        by_comp = self._by_composite
        deg = self._prime_degree
        max_bits = self.max_bits
        limit = 1 << max_bits
        wide = self.wide
        rid = self._next_id
        out: List[Relationship] = []
        try:
            for primes in groups:
                pset = frozenset(map(int, primes))
                if len(pset) < 2:
                    raise ValueError(
                        "a relationship needs >= 2 distinct elements")
                if len(pset) == 2:
                    # pairwise fast path — the dominant case (FK pairs,
                    # chain edges): inline the two-prime chunking;
                    # identical chunk tuple, with invalid pairs deferred
                    # to the canonical encoder for the canonical error
                    a, b = pset
                    if a > b:
                        a, b = b, a
                    if a <= 1 or b >= limit or b >= MAX_PRIME_LIMIT:
                        encode_relationship(pset, max_bits)  # raises
                        raise AssertionError("unreachable")
                    ab = a * b
                    comps = (ab,) if ab < limit else (a, b)
                else:
                    comps = tuple(encode_relationship(pset, max_bits))
                rel = Relationship(rid, pset, comps, kind, weight)
                rid += 1
                by_id[rel.rel_id] = rel
                for c in comps:
                    by_comp[c] = rel.rel_id
                for p in pset:
                    deg[p] = deg.get(p, 0) + 1
                out.append(rel)
        finally:
            self._next_id = rid
            if out:
                self._dirty = True
                self.version += len(out)
        return out

    def unregister(self, rel_id: int) -> None:
        rel = self._by_id.pop(rel_id, None)
        if rel is None:
            return
        for c in rel.composites:
            self._by_composite.pop(c, None)
        for p in rel.primes:
            d = self._prime_degree.get(p, 0) - 1
            if d <= 0:
                self._prime_degree.pop(p, None)
            else:
                self._prime_degree[p] = d
        self._dirty = True
        self.version += 1

    def drop_prime(self, p: int) -> List[int]:
        """Remove every relationship involving prime p (prime recycling
        must purge stale composites or factorization would resurrect a
        recycled element — paper §7.2 'prime space management')."""
        doomed = [r.rel_id for r in self._by_id.values() if p in r.primes]
        for rid in doomed:
            self.unregister(rid)
        return doomed

    # -- queries -------------------------------------------------------------

    def __len__(self) -> int:
        return len(self._by_id)

    @property
    def n_composites(self) -> int:
        return len(self._by_composite)

    def degree(self, p: int) -> int:
        return self._prime_degree.get(p, 0)

    def primes_array(self) -> np.ndarray:
        """Sorted int64 array of every live member prime — the trial-
        division pool for the batched factorize kernel (engine bulk
        discovery, DESIGN.md §3)."""
        return np.fromiter(sorted(self._prime_degree), dtype=np.int64,
                           count=len(self._prime_degree))

    def composites_array(self) -> np.ndarray:
        """Flat int64 array of all live composites (host arithmetic).

        Narrow mode only — wide chunks cannot fit int64;
        use :meth:`limbs_array` (kernels) or :meth:`composites_view` /
        :meth:`composites_list` (host) there.
        """
        if self.wide:
            raise OverflowError(
                "composites exceed int64 in wide mode; use "
                "limbs_array() / composites_view() / composites_list()")
        if self._dirty:
            self._arr = np.fromiter(self._by_composite.keys(), dtype=np.int64,
                                    count=len(self._by_composite))
            self._dirty = False
        return self._arr

    def composites_list(self) -> List[int]:
        """All live composites as exact Python ints, registry order."""
        return [int(c) for c in self._by_composite]

    def composites_view(self) -> np.ndarray:
        """Registry-order composite array at whatever dtype is exact:
        the int64 host view in narrow mode, an object array of Python
        ints in wide mode.  Host-side consumers that only index / compare
        / take ``%`` (resharding, isolation audit) stay mode-agnostic."""
        if not self.wide:
            return self.composites_array()
        out = np.empty(len(self._by_composite), dtype=object)
        for i, c in enumerate(self._by_composite):
            out[i] = int(c)
        return out

    def limbs_array(self) -> np.ndarray:
        """``(N, n_limbs)`` int32 little-endian digit matrix of all live
        composites, registry (row) order matching
        :meth:`composites_view` — the kernel input in both modes."""
        if self._limbs_version != self.version:
            self._limbs = pack_limbs(list(self._by_composite), self.n_limbs)
            self._limbs_version = self.version
        return self._limbs

    def relationship_of_composite(self, c: int) -> Optional[Relationship]:
        rid = self._by_composite.get(c)
        return self._by_id.get(rid) if rid is not None else None

    def containing(self, p: int) -> List[Relationship]:
        """All relationships whose composite is divisible by p.

        This is the paper's §4.2 scan: divisibility test over the registry,
        then *factorization* of the matching composites recovers the exact
        member set (not a reverse-index lookup — the correctness of the
        factorization path is the claim under test, and the scan is what
        the TPU kernel accelerates).
        """
        if self.wide:
            # exact Python-int modular scan (dict insertion order == the
            # registry order the narrow numpy path iterates in)
            hits: Sequence[int] = [c for c in self._by_composite if c % p == 0]
        else:
            arr = self.composites_array()
            if arr.size == 0:
                return []
            hits = arr[arr % p == 0]
        out: List[Relationship] = []
        seen: Set[int] = set()
        for c in hits:
            c = int(c)
            factors = self._factor_with_hint(c, p)
            assert p in factors, "divisibility hit must contain p (Theorem 1)"
            rid = self._by_composite[c]
            if rid not in seen:
                seen.add(rid)
                out.append(self._by_id[rid])
        return out

    def _factor_with_hint(self, c: int, p: int) -> Tuple[int, ...]:
        """Factor c given the known factor p from the divisibility scan.

        The scan *is* trial division by pool primes (Algorithm 2 stage 1):
        once p is known, the cofactor c//p is either 1, prime (pairwise
        relationship — the dominant case), or recursed through the full
        multi-stage factorizer.  Stage stats are charged accordingly.
        """
        from .primes import is_prime  # local import avoids cycle at module load

        cached = self.factorizer.cache.get(c)
        if cached is not None and p in cached:
            self.factorizer.stats.cache_hits += 1
            self.factorizer.stats.total += 1
            return tuple(sorted(set(cached)))
        q, r = divmod(c, p)
        assert r == 0
        self.factorizer.stats.total += 1
        self.factorizer.stats.trial_division += 1
        if q == 1:
            out = (p,)
        elif is_prime(q):
            out = (p, q)
        else:
            # generous budget: registry hits must decode exactly (partial
            # factorizations are never cached — see Factorizer.factorize)
            out = tuple(sorted({p, *self.factorizer.factorize(
                q, time_budget_s=1.0)}))
        self.factorizer.cache.put(c, out)
        return out

    def related_primes(self, p: int) -> Set[int]:
        """All primes deterministically related to p (excluding p)."""
        rel: Set[int] = set()
        for r in self.containing(p):
            for c in r.composites:
                for q in self.factorizer.distinct_factors(int(c)):
                    if q != p:
                        rel.add(q)
            # multi-chunk relationships: all member primes are related
            rel |= set(r.primes) - {p}
        return rel

    def limb_composite(self, c: int) -> LimbComposite:
        """The registry-width :class:`LimbComposite` encoding of one
        composite (a single row of :meth:`limbs_array`)."""
        return LimbComposite.encode(int(c), self.n_limbs)

    def decode(self, c: int) -> Tuple[int, ...]:
        """Factorize an arbitrary composite back to its member primes."""
        return self.factorizer.distinct_factors(int(c))
