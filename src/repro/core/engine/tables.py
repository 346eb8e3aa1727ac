"""Static discovery tables for the vectorized PFCS engine.

PFCS relationships are registered at schema/catalog time (the database
knows its FK constraints, the trainer its batch composition) and are
immutable while a trace replays.  Everything the oracle's
``IntelligentPrefetcher.decide`` computes per access is therefore a pure
function of the key, and collapses to three arrays:

    targets : (K, budget) int32 — weight-ranked prefetch targets, -1 pad
    truth   : (K, budget) bool  — target truly related (ground truth)
    degree  : (K,) int32        — live relationship degree (victim policy)

Two discovery backends build the SAME target table:

  * ``discover="host"``   — replays ``IntelligentPrefetcher.decide`` per
    distinct accessed key.  Charges the host factorizer's stage mix
    (table/cache/trial/rho) exactly as the scalar simulation would, so
    engine ``AccessStats.factor_ops`` match the oracle's.
  * ``discover="kernel"`` — bulk path through the Pallas kernels
    (:func:`repro.kernels.ops.divisibility_scan` for the §4.2 registry
    scan, :func:`repro.kernels.ops.factorize_batch` for Algorithm 2
    stage 1 decode).  This is the TPU registry-refresh deployment; the
    decoded factorizations seed the host factorization cache and the
    stage mix reflects the kernel doing the work (trial for each first
    decode, cache thereafter — the rho tail is subsumed by the kernel).

Both backends produce bit-identical target ORDER: candidates are
deduplicated in registry (composite-array) order and ranked by weight
with a stable sort — the exact iteration order of the oracle
(``tests/test_engine.py::test_kernel_and_host_tables_agree``).
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from repro.obs import profile

from ..pfcs_cache import PFCSCache
from ..traces import Trace

__all__ = ["PFCSTables", "pfcs_tables", "related_bulk", "make_pfcs_cache",
           "successor_table", "rows_from_hits", "keyed_queries"]


class PFCSTables(NamedTuple):
    """Precomputed engine inputs for one (trace, PFCS config) pair."""

    targets: np.ndarray          # (K, budget) int32, -1 padded
    truth: np.ndarray            # (K, budget) bool
    degree: np.ndarray           # (K,) int32
    factor_ops: Dict[str, int]   # stage -> op count (latency model input)
    cache: PFCSCache             # the registered host cache (introspection)


def make_pfcs_cache(trace: Trace,
                    capacities: Sequence[Tuple[str, int]],
                    prefetch_budget: int = 4,
                    victim_window: int = 8,
                    enable_prefetch: bool = True,
                    prefetch_trigger: str = "miss") -> PFCSCache:
    """Host cache with the trace's relationships registered — the same
    schema-time setup ``simulate_pfcs`` performs (prime assignment order
    and therefore every composite is identical)."""
    cache = PFCSCache(capacities, prefetch_budget=prefetch_budget,
                      enable_prefetch=enable_prefetch,
                      victim_window=victim_window,
                      prefetch_trigger=prefetch_trigger)
    for grp in trace.relationships:
        cache.register_relationship(grp, kind=trace.meta.get("kind", "generic"))
    return cache


def keyed_queries(assigner, data_ids: Sequence[int]) -> List[Tuple[int, int]]:
    """Query routing: ``(data id, prime)`` for every id with an assigned
    prime, in the given order (ids without one discover nothing — the
    oracle's early return)."""
    return [(int(d), p) for d in data_ids
            if (p := assigner.prime_of(int(d))) is not None]


def _relationships(rel_of, composites: List[int]) -> list:
    """The relationships of ``composites`` (hits in ascending registry
    position), each once, in registry order."""
    rels, seen = [], set()
    for c in composites:
        rel = rel_of(c)
        if rel is not None and rel.rel_id not in seen:
            seen.add(rel.rel_id)
            rels.append(rel)
    return rels


def _expand(rels, p: int, data_of) -> List[int]:
    """The other members of each relationship, as data ids, in
    ``rel.primes`` order (the oracle's frozenset order); repeats kept."""
    row: List[int] = []
    for rel in rels:
        for q in rel.primes:
            if q != p:
                succ = data_of(q)
                if succ is not None:
                    row.append(succ)
    return row


def rows_from_hits(registry, assigner, keyed: Sequence[Tuple[int, int]],
                   hits: Sequence[np.ndarray]) -> Dict[int, List[int]]:
    """Successor rows from scan hits, in the oracle's order.

    ``hits[i]`` holds the ascending registry positions whose composite
    ``keyed[i]``'s prime divides.  Each row walks those composites'
    relationships in registry order, each relationship once, and expands
    it in ``rel.primes`` order; repeated targets are kept (the touch-time
    residency check skips them).  The one-chip kernel path and the
    sharded path both build their rows here.
    """
    comps = registry.composites_view()
    rel_of, data_of = registry.relationship_of_composite, assigner.data_of
    return {d: _expand(_relationships(rel_of, comps[idxs].tolist()), p,
                       data_of)
            for (d, p), idxs in zip(keyed, hits)}


def _check_hits(primes: np.ndarray, hits: Sequence[np.ndarray],
                needed: np.ndarray, factors: Sequence[Sequence[int]]) -> None:
    """Theorem 1: every (query prime, hit position) pair of the scan is a
    (factor, composite) pair of the decoded factorizations."""
    hit = (np.concatenate(hits) << 32) | np.repeat(
        primes, [len(h) for h in hits])
    decoded = (np.repeat(needed, [len(f) for f in factors]) << 32) | \
        np.fromiter((q for f in factors for q in f), np.int64)
    assert np.isin(hit, decoded).all(), "divisibility hit must contain p"


def _kernel_hits(keyed: Sequence[Tuple[int, int]], digits: np.ndarray,
                 pool: np.ndarray, chunk: int):
    """The kernel passes of a bulk discovery: the §4.2 divisibility scan
    of the digit matrix in ``chunk``-query slices, then one
    ``factorize_batch`` over the sorted unique candidates, checked
    against the hits (Theorem 1).

    Returns ``(hits, needed, factors)``: per query the ascending hit
    positions, the candidate positions, and their decoded factors.
    """
    # looked up on the module at call time: a caller may wrap them
    from repro.kernels.ops import divisibility_scan, factorize_batch

    primes = np.asarray([p for _, p in keyed], dtype=np.int64)
    hits: List[np.ndarray] = []
    for lo in range(0, len(primes), chunk):
        hits.extend(divisibility_scan(digits, primes[lo:lo + chunk]))
    with profile.span("tables.candidates"):
        needed = np.unique(np.concatenate(hits))
    profile.count(candidates=len(needed))
    factors: List[List[int]] = []
    if needed.size:
        factors, residual = factorize_batch(digits[needed], pool)
        assert all(r == 1 for r in residual), \
            "registry composite escaped its pool"
    _check_hits(primes, hits, needed, factors)
    return hits, needed, factors


def related_bulk(cache: PFCSCache, keys: Sequence[int],
                 chunk: int = 1024) -> Dict[int, List[Tuple[int, float]]]:
    """Bulk relationship discovery through the Pallas kernels.

    For every key with an assigned prime: divisibility-scan the live
    composite registry (§4.2), decode each matching composite with the
    batched trial-division kernel, and return the weight-ranked related
    elements — the device twin of
    ``IntelligentPrefetcher.related_elements``, with identical ordering.
    The scan, decode and check are :func:`successor_table`'s.
    """
    registry = cache.registry
    assigner = cache.assigner
    arr = registry.composites_view()
    keyed = keyed_queries(assigner, keys)
    if arr.size == 0 or not keyed:
        return {}

    hits, needed, factors = _kernel_hits(keyed, registry.limbs_array(),
                                         registry.primes_array(), chunk)
    if needed.size:
        stats = cache.factorizer.stats
        for c, fs in zip(arr[needed].tolist(), factors):
            cache.factorizer.cache.put(int(c), tuple(sorted(fs)))
        # stage accounting: the kernel's trial division decodes each
        # composite once; every further (prime, composite) incidence is a
        # factorization-cache hit (DESIGN.md §3)
        incidences = sum(len(idxs) for idxs in hits)
        stats.trial_division += len(needed)
        stats.cache_hits += incidences - len(needed)
        stats.total += incidences

    rel_of, data_of = registry.relationship_of_composite, assigner.data_of
    out: Dict[int, List[Tuple[int, float]]] = {}
    for (k, p), idxs in zip(keyed, hits):
        ranked: Dict[int, float] = {}
        for rel in _relationships(rel_of, arr[idxs].tolist()):
            for tgt in _expand((rel,), p, data_of):
                ranked[tgt] = max(ranked.get(tgt, 0.0), rel.weight)
        out[k] = sorted(ranked.items(), key=lambda kv: -kv[1])
    return out


def successor_table(registry, assigner, data_ids: Sequence[int],
                    discover: str = "host",
                    chunk: int = 1024) -> Dict[int, List[int]]:
    """Bulk successor-discovery table for chain-style registries.

    The serving paged-KV cache's prefetch loop
    (``repro.serving.kv_cache.PagedKVCache._prefetch_successors``)
    walks, per touched page, every relationship containing the page's
    prime and collects the *other* members as prefetch candidates.  The
    candidate ORDER is the oracle's exact iteration order — composite
    registry (registration) order, deduplicated by relationship, then
    ``rel.primes`` iteration — and the list is deliberately NOT
    deduplicated by target: the dynamic residency check at touch time
    is what skips repeats, so repeats must survive into the table.

    Two backends build the SAME table:

      * ``discover="host"``   — replays ``registry.containing`` per id
        (charging the host factorizer exactly as the scalar cache does);
        the reference the kernel rows are tested against;
      * ``discover="kernel"`` — one bulk pass through the Pallas
        ``divisibility_scan`` / ``factorize_batch`` kernels, the TPU
        registry-refresh deployment, then :func:`rows_from_hits`.

    Returns ``{data_id: [successor data_id, ...]}`` for every id that
    has an assigned prime (ids without one discover nothing — exactly
    the oracle's early return).
    """
    if discover not in ("host", "kernel"):
        raise ValueError(f"discover must be 'host' or 'kernel', "
                         f"got {discover!r}")
    with profile.span("tables.pack"):
        keyed = keyed_queries(assigner, data_ids)
        n = registry.n_composites
        kernel = discover == "kernel" and n > 0 and bool(keyed)
        if kernel:
            pool = registry.primes_array()
            digits = registry.limbs_array()
    # counts go to the span open around the call (``pages.refresh``)
    profile.count(queries=len(keyed), composites=n,
                  pool=len(pool) if kernel else 0)
    if discover == "host":
        with profile.span("tables.rows"):
            return {d: _expand(registry.containing(p), p, assigner.data_of)
                    for d, p in keyed}
    if not kernel:
        return {d: [] for d, _ in keyed}

    hits, _, _ = _kernel_hits(keyed, digits, pool, chunk)
    with profile.span("tables.rows"):
        return rows_from_hits(registry, assigner, keyed, hits)


def pfcs_tables(trace: Trace,
                capacities: Sequence[Tuple[str, int]],
                prefetch_budget: int = 4,
                victim_window: int = 8,
                enable_prefetch: bool = True,
                prefetch_trigger: str = "miss",
                discover: str = "host",
                n_keys: Optional[int] = None) -> PFCSTables:
    """Build the engine's discovery tables for one trace."""
    cache = make_pfcs_cache(trace, capacities, prefetch_budget,
                            victim_window, enable_prefetch, prefetch_trigger)
    K = int(n_keys if n_keys is not None else
            max(trace.n_keys, int(trace.accesses.max(initial=0)) + 1))
    B = max(1, int(prefetch_budget))
    targets = np.full((K, B), -1, dtype=np.int32)
    truth = np.zeros((K, B), dtype=bool)
    related = trace.related_map()

    f = cache.factorizer.stats
    base = (f.table_hits, f.cache_hits, f.trial_division, f.pollard_rho)

    if enable_prefetch:
        # first-occurrence order: the host factorizer's cofactor cache is
        # order-sensitive when composites share cofactors, and the scalar
        # oracle pays each key's discovery cost at its FIRST access
        acc = np.asarray(trace.accesses)
        _, first = np.unique(acc, return_index=True)
        distinct = [int(k) for k in acc[np.sort(first)]]
        if discover == "kernel":
            ranked_map = related_bulk(cache, distinct)
            per_key = {k: [t for t, _ in ranked_map.get(k, [])][:B]
                       for k in distinct}
        elif discover == "host":
            per_key = {k: [d.target for d in cache.prefetcher.decide(k)][:B]
                       for k in distinct}
        else:
            raise ValueError(f"discover must be 'host' or 'kernel', "
                             f"got {discover!r}")
        for k, tgts in per_key.items():
            rel_k = related.get(k, ())
            for j, tgt in enumerate(tgts):
                targets[k, j] = int(tgt)
                truth[k, j] = int(tgt) in rel_k

    degree = np.zeros((K,), dtype=np.int32)
    for k in range(K):
        p = cache.assigner.prime_of(k)
        if p is not None:
            degree[k] = cache.registry.degree(p)

    f = cache.factorizer.stats
    factor_ops = {
        "table": f.table_hits - base[0],
        "cache": f.cache_hits - base[1],
        "trial": f.trial_division - base[2],
        "rho": f.pollard_rho - base[3],
    }
    return PFCSTables(targets, truth, degree, factor_ops, cache)
