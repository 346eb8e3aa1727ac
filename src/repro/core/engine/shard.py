"""Mesh-sharded PFCS discovery: partitioned prime spaces + shard_map scans.

PR 1-2 vectorized the simulator and the serving cache on ONE device;
this module distributes the *PFCS state itself* — the prime space and
the composite registry — across a ``("data", "model")`` device mesh so
bulk relationship discovery scales with shard count (DESIGN.md §6).

**Prime-space partition.**  :class:`PrimeSpacePartition` carves every
cache level's prime range (``core.primes.LEVEL_PRIME_RANGES``) into
contiguous value blocks dealt round-robin to shards: each shard owns a
striped family of contiguous prime ranges.  Contiguity keeps each
block's factorization locality (neighbouring chain pages get
neighbouring primes under Algorithm 1's ascending allocation); striping
keeps ownership balanced even though allocation is ascending.  Ownership
is a pure O(1) function of the prime value — no directory, no
coordination — so every shard can classify any composite locally.

**Sharded registry classification.**  A relationship whose member
primes all fall in one shard's ranges is *shard-local*: its composite
chunks live only in that shard's registry slice and are scanned only
there.  A relationship straddling prime ranges (a chain edge whose two
page primes have different owners) is *cross-shard*: its chunks go to
the exchanged slice that every shard scans.  Classification preserves
the global registry (registration) order — the candidate-order contract
the serving cache's parity tests pin down.

**Per-shard bulk discovery under shard_map.**  Successor rows are
rebuilt per shard through the SAME Pallas kernels the single-device
path uses (``divisibility_mask_pallas`` for the §4.2 scan), mapped over
the mesh with ``shard_map``: every shard scans its own registry slice
against its own query primes.  Cross-shard relationships are resolved
by a **collective batched-gcd exchange**: each shard contributes its
slice of the cross-shard composites, ``lax.all_gather`` replicates them
along the mesh, and each shard computes ``gcd_squarefree_pallas`` of
its *query chunk products* (its owned query primes packed into
registry-width composites) against every gathered composite.  A gcd > 1 decodes — exactly, by
unique factorization — to the member primes the shard owns, so no
per-query modulo scan ever crosses shard boundaries.

When the host exposes fewer devices than shards (the common laptop
case), the same math runs as a per-shard host loop over the identical
kernels — bit-identical tables, no mesh required.  CI exercises the
real ``shard_map`` path on a forced multi-device CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=2``).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.obs import profile
from repro.obs.profile import kernel_scope
from repro.sharding.stripes import BlockStripes

from ..composite import encode_relationship
from ..primes import CacheLevel, LEVEL_PRIME_RANGES

__all__ = ["PrimeSpacePartition", "shard_mesh", "sharded_successor_table",
           "ShardScanReport"]


class PrimeSpacePartition:
    """Deterministic owner function: prime value -> shard id.

    Each bounded level range ``(lo, hi)`` is split into contiguous value
    blocks of width ``min((hi - lo + 1) // (n_shards * stripes_per_shard),
    cap)``; block ``k`` belongs to shard ``k % n_shards``.  The unbounded
    MEM range uses the fixed cap width.  ``n_shards == 1`` degenerates to
    "shard 0 owns everything" (the single-device mesh case).

    The block machinery itself — contiguous value blocks, round-robin
    striping, per-level width caps, vectorized ownership — is the shared
    :class:`repro.sharding.stripes.BlockStripes` partitioner (the tenant
    namespace layer stripes the same prime space over tenants with it).
    """

    def __init__(self, n_shards: int, stripes_per_shard: int = 8):
        self.stripes = BlockStripes(n_shards, LEVEL_PRIME_RANGES,
                                    stripes_per_part=stripes_per_shard)
        self.n_shards = self.stripes.n_parts
        self.stripes_per_shard = self.stripes.stripes_per_part
        self._blocks: Dict[int, Tuple[int, int]] = self.stripes._blocks

    def _level_of(self, p: int) -> int:
        return self.stripes.level_of(p)

    def owner(self, p: int) -> int:
        """Shard owning prime ``p`` — pure function, O(1), no state."""
        return self.stripes.owner(p)

    def owners(self, primes: Sequence[int]) -> np.ndarray:
        return self.stripes.owners(primes)

    def classify(self, registry) -> Tuple[List[List[int]], List[int]]:
        """Split the live registry into per-shard-local and cross-shard
        composite *positions* (indices into ``registry.composites_array()``
        — global registration order, which both scan paths preserve).

        A relationship is local to shard ``s`` iff every member prime is
        owned by ``s``; otherwise every chunk of it is cross-shard.
        """
        arr = registry.composites_view()
        local: List[List[int]] = [[] for _ in range(self.n_shards)]
        cross: List[int] = []
        for pos in range(arr.size):
            rel = registry.relationship_of_composite(int(arr[pos]))
            if rel is None:                       # pragma: no cover - defensive
                continue
            owners = {self.owner(q) for q in rel.primes}
            if len(owners) == 1:
                local[owners.pop()].append(pos)
            else:
                cross.append(pos)
        return local, cross

    def describe(self) -> str:
        parts = [f"{CacheLevel.NAMES[lvl]}:block={w}"
                 for lvl, (_, w) in sorted(self._blocks.items())]
        return (f"PrimeSpacePartition(n_shards={self.n_shards}, "
                f"stripes={self.stripes_per_shard}, {', '.join(parts)})")


def shard_mesh(n_shards: int):
    """A ``("data", "model")`` mesh with ``data * model == n_shards`` over
    the locally visible devices, or ``None`` when the host does not expose
    enough devices (callers then use the bit-identical host loop).

    The model axis takes the largest divisor of ``n_shards`` that is
    <= sqrt(n_shards) — 1 shard -> (1, 1), 2 -> (2, 1), 4 -> (2, 2) —
    mirroring ``launch.mesh.make_production_mesh``'s square-ish layout.
    """
    import jax

    if n_shards < 1:
        raise ValueError("n_shards must be >= 1")
    if len(jax.devices()) < n_shards:
        return None
    model = 1
    for m in range(int(n_shards ** 0.5), 0, -1):
        if n_shards % m == 0:
            model = m
            break
    return jax.make_mesh((n_shards // model, model), ("data", "model"))


def _pad_rows(rows: Sequence[np.ndarray], mult: int, fill: int,
              dtype=np.int32) -> np.ndarray:
    """Stack ragged 1-D arrays into (S, W), W bucketed to ``mult * 2**k``
    — power-of-two buckets bound the number of distinct compiled shapes
    as tables grow across refreshes."""
    need = max([r.shape[0] for r in rows] + [1])
    width = mult
    while width < need:
        width *= 2
    out = np.full((len(rows), width), fill, dtype=dtype)
    for i, r in enumerate(rows):
        out[i, :r.shape[0]] = r
    return out


def _pad_digit_stack(rows: Sequence[np.ndarray], mult: int, L: int,
                     width: Optional[int] = None) -> np.ndarray:
    """Stack ragged (n_i, L) digit matrices into (S, W, L); pad rows
    encode the value 1 (match nothing) and W is bucketed to
    ``mult * 2**k`` like :func:`_pad_rows`."""
    need = max([r.shape[0] for r in rows] + [1])
    if width is None:
        width = mult
        while width < need:
            width *= 2
    out = np.zeros((len(rows), width, L), dtype=np.int32)
    out[:, :, 0] = 1
    for i, r in enumerate(rows):
        if r.shape[0]:
            out[i, :r.shape[0], :] = r
    return out


@dataclass
class ShardScanReport:
    """Per-refresh work split (benchmark / introspection output)."""

    n_shards: int = 0
    used_shard_map: bool = False
    local_composites: List[int] = field(default_factory=list)
    cross_composites: int = 0
    queries_per_shard: List[int] = field(default_factory=list)
    gcd_pairs: int = 0


def _one_shard_scan(lc, qs, ck, pool, gathered_cross, *, n_chunks: int,
                    interpret: bool):
    """One shard's kernel work: local divisibility mask + cross gcds.

    ``lc`` (C, L) local digit rows, ``qs`` (Q,) owned query primes,
    ``ck`` (K, L) query chunk products, ``gathered_cross`` (X, L) every
    shard's cross composites.  The gcd pool is the shard's own
    (deduplicated, zero-padded) query primes — chunk products are
    products of exactly those primes, so the pool covers every possible
    common factor.
    """
    import jax.numpy as jnp

    from repro.kernels.factorize import divisibility_mask_pallas
    from repro.kernels.gcd import gcd_squarefree_pallas

    gcd_block = 256
    mask = divisibility_mask_pallas(lc, qs, interpret=interpret)
    # batched-gcd exchange: every query chunk x every cross composite
    x, L = gathered_cross.shape
    a = jnp.repeat(ck, x, axis=0)                       # (K*X, L)
    b = jnp.tile(gathered_cross, (n_chunks, 1))
    pad = (-a.shape[0]) % gcd_block
    one = jnp.broadcast_to((jnp.arange(L) == 0).astype(a.dtype), (pad, L))
    a = jnp.concatenate([a, one])
    b = jnp.concatenate([b, one])
    g = gcd_squarefree_pallas(a, b, pool, block_n=gcd_block,
                              interpret=interpret)
    return mask, g[:n_chunks * x].reshape(n_chunks, x, L)


@functools.lru_cache(maxsize=64)
def _shard_map_scan(mesh, shapes: Tuple[int, ...], interpret: bool):
    """Compiled shard_map scan, memoized per (mesh, bucketed shapes)."""
    import jax

    from repro.sharding.partition import shard_stack_spec

    axes = tuple(mesh.axis_names)
    spec = shard_stack_spec(mesh)       # leading shard axis over data x model
    _, _, K, _, _ = shapes

    def body(lc, qs, ck, pool, xc):
        gathered = jax.lax.all_gather(xc[0], axes, tiled=True)
        mask, g = _one_shard_scan(lc[0], qs[0], ck[0], pool[0], gathered,
                                  n_chunks=K, interpret=interpret)
        return mask[None], g[None]

    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(spec,) * 5,
                                 out_specs=(spec, spec), check_vma=False))


def _scan_sharded(local_c: np.ndarray, queries: np.ndarray,
                  chunks: np.ndarray, pools: np.ndarray,
                  cross_c: np.ndarray,
                  mesh) -> Tuple[np.ndarray, np.ndarray]:
    """The per-shard kernel work: local divisibility masks + cross gcds.

    Inputs are padded stacks: (S, C, L) local digit rows, (S, Q) query
    primes, (S, K, L) query-chunk digits, (S, P) gcd pools and (S, X, L)
    cross slices; returns ``(local_mask (S, C, Q), gcd digits (S, K, X,
    L))``.  With a mesh of exactly S devices the work runs under
    ``shard_map`` (one shard per device, cross composites replicated by
    ``lax.all_gather`` — the collective exchange); otherwise a host loop
    runs the identical kernels per shard.
    """
    import jax.numpy as jnp

    from repro.kernels.ops import check_32bit, interpret_mode

    interpret = interpret_mode()
    check_32bit()
    S, C, L = local_c.shape
    Q = queries.shape[1]
    K = chunks.shape[1]

    with kernel_scope("sharded_gcd_exchange", items=S * C, shards=S,
                      c=C, q=Q, chunks=K):
        if mesh is not None and mesh.size == S:
            fn = _shard_map_scan(
                mesh, (C, Q, K, pools.shape[1], cross_c.shape[1]), interpret)
            mask, g = fn(jnp.asarray(local_c), jnp.asarray(queries),
                         jnp.asarray(chunks), jnp.asarray(pools),
                         jnp.asarray(cross_c))
        else:                           # host loop, same kernels, same math
            gathered = jnp.asarray(cross_c.reshape(-1, L))
            masks, gs = [], []
            for s in range(S):
                m, g = _one_shard_scan(
                    jnp.asarray(local_c[s]), jnp.asarray(queries[s]),
                    jnp.asarray(chunks[s]), jnp.asarray(pools[s]), gathered,
                    n_chunks=K, interpret=interpret)
                masks.append(m)
                gs.append(g)
            mask, g = jnp.stack(masks), jnp.stack(gs)
        with profile.span("kernels.sharded_gcd_exchange.fetch"):
            return np.asarray(mask), np.asarray(g)


def sharded_successor_table(registry, assigner, data_ids: Sequence[int],
                            partition: PrimeSpacePartition,
                            mesh=None,
                            report: Optional[ShardScanReport] = None,
                            precomputed: Optional[Tuple[List[List[int]],
                                                        List[int]]] = None,
                            ) -> Dict[int, List[int]]:
    """Mesh-partitioned twin of :func:`repro.core.engine.successor_table`.

    Produces BIT-IDENTICAL rows (same candidates, same order — global
    registry order, deduplicated by relationship, expanded in
    ``rel.primes`` order) while splitting the scan work by prime
    ownership: each shard's Pallas divisibility scan touches only its
    local registry slice, and only cross-shard relationships ride the
    collective gcd exchange.

    ``precomputed`` optionally supplies the ``(local_pos, cross_pos)``
    registry split (e.g. the maintained
    :class:`repro.sharding.reshard.ShardSlices` index) instead of the
    O(registry) :meth:`PrimeSpacePartition.classify` walk.  Any split
    that routes each position to a shard owning one of its chunk's
    primes yields identical rows — a prime's hits can only come from the
    chunk containing it.
    """
    from repro.kernels.ops import factorize_batch, prime_lanes

    from ..composite import pack_limbs, unpack_limbs

    S = partition.n_shards
    keyed = [(int(d), p) for d in data_ids
             if (p := assigner.prime_of(int(d))) is not None]
    arr = registry.composites_view()
    if arr.size == 0 or not keyed:
        return {d: [] for d, _ in keyed}

    with profile.span("tables.pack", queries=len(keyed),
                      composites=int(arr.size)):
        # ---- partition state: registry slices and query routing --------- #
        if precomputed is not None:
            local_pos, cross_pos = precomputed
        else:
            local_pos, cross_pos = partition.classify(registry)
        by_shard: List[List[Tuple[int, int]]] = [[] for _ in range(S)]
        for d, p in keyed:
            by_shard[partition.owner(p)].append((d, p))

        queries = _pad_rows([prime_lanes([p for _, p in sh])
                             for sh in by_shard], 512, 0)
        # query chunk products: each shard's owned query primes packed into
        # registry-width composites — the gcd exchange payload (one wide
        # chunk usually covers the whole shard's query set)
        chunk_vals: List[List[int]] = []
        for sh in by_shard:
            ps = {p for _, p in sh}
            chunk_vals.append(encode_relationship(ps, registry.max_bits)
                              if ps else [])
        # per-shard cross-slice width bucketed to powers of two, like every
        # other stack: an exact ceil(cross/S) width would change the compiled
        # shard_map shape on nearly every registry growth
        need = -(-max(len(cross_pos), 1) // S)
        per = 8
        while per < need:
            per *= 2

        digits = registry.limbs_array()
        L = registry.n_limbs
        local_c = _pad_digit_stack(
            [digits[np.asarray(pos, dtype=np.int64)]
             if pos else np.empty((0, L), np.int32)
             for pos in local_pos], 256, L)
        chunks = _pad_digit_stack([pack_limbs(cv, L) for cv in chunk_vals],
                                  1, L)
        # the gcd-reconstruction pool: each shard's deduplicated query primes
        # (zero-padded) — exactly the primes its chunks contain
        pools = _pad_rows([prime_lanes(sorted({p for _, p in sh}))
                           for sh in by_shard], 512, 0)
        cross_digits = (digits[np.asarray(cross_pos, dtype=np.int64)]
                        if cross_pos else np.empty((0, L), np.int32))
        cross_sh = _pad_digit_stack(
            [cross_digits[s * per:(s + 1) * per] for s in range(S)],
            1, L, width=per)

    # ---- kernel work (shard_map when the mesh matches) ------------------ #
    mask, gcds = _scan_sharded(local_c, queries, chunks, pools, cross_sh,
                               mesh)

    if report is not None:
        report.n_shards = S
        report.used_shard_map = mesh is not None and mesh.size == S
        report.local_composites = [len(p) for p in local_pos]
        report.cross_composites = len(cross_pos)
        report.queries_per_shard = [len(sh) for sh in by_shard]
        report.gcd_pairs = int(chunks.shape[1] * S * per)

    with profile.span("tables.candidates"):
        # ---- decode the gcd exchange: which cross composites contain which
        # owned query primes (exact — unique factorization) --------------- #
        cross_of_prime: Dict[int, List[int]] = {}
        for s in range(S):
            if not by_shard[s] or not cross_pos:
                continue
            pool = sorted({p for _, p in by_shard[s]})
            gs = gcds[s]                        # (K, X, L) digit rows
            # value > 1 iff digit 0 > 1 or any higher digit nonzero
            hit_k, hit_x = np.nonzero((gs[..., 0] > 1)
                                      | (gs[..., 1:] != 0).any(axis=-1))
            valid = hit_x < len(cross_pos)      # drop padding columns
            hit_k, hit_x = hit_k[valid], hit_x[valid]
            hit_vals = unpack_limbs(gs[hit_k, hit_x])
            uniq = sorted(set(hit_vals))
            if not uniq:
                continue
            facs, residual = factorize_batch(uniq, pool)
            assert all(r == 1 for r in residual), \
                "gcd escaped the shard's query pool"
            fac_of = {g: fs for g, fs in zip(uniq, facs)}
            for x, v in zip(hit_x, hit_vals):
                for q in fac_of[v]:
                    cross_of_prime.setdefault(int(q), []).append(int(x))

    with profile.span("tables.rows"):
        # ---- assemble rows in the oracle's exact order ------------------ #
        out: Dict[int, List[int]] = {}
        for s in range(S):
            pos_map = local_pos[s]
            for col, (d, p) in enumerate(by_shard[s]):
                hits = [pos_map[i] for i in
                        np.nonzero(mask[s, :len(pos_map), col])[0]]
                hits.extend(cross_pos[x] for x in cross_of_prime.get(p, ()))
                row: List[int] = []
                seen: set = set()
                for pos in sorted(hits):        # ascending == registry order
                    rel = registry.relationship_of_composite(int(arr[pos]))
                    if rel is None or rel.rel_id in seen:
                        continue
                    seen.add(rel.rel_id)
                    for q in rel.primes:        # oracle's frozenset order
                        if q == p:
                            continue
                        succ = assigner.data_of(q)
                        if succ is not None:
                            row.append(succ)
                out[d] = row
    return out
