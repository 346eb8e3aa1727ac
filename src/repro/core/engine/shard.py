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
coordination — so every shard can place any composite locally.

**The registry split.**  :class:`repro.sharding.reshard.ShardSlices`
labels each composite chunk by the owners of the primes dividing it: a
chunk whose primes one shard owns is *shard-local* and scanned only
there; a chunk whose primes span owners (a chain edge whose two page
primes have different owners) is *cross-shard* and goes to the
exchanged slice every shard scans.  A prime's hits can only come from a
chunk that contains it, so the split loses no (query prime, position)
hit.  The index is kept current as the registry grows and preserves
the global registry (registration) order — the candidate-order
contract the serving cache's parity tests pin down.

**Per-shard bulk discovery under shard_map.**  Successor rows are
rebuilt per shard through the SAME program the single-device path uses
(``divisibility_hits_pallas``: the §4.2 scan, its hits compacted on the
device), mapped over the mesh with ``shard_map``.  Cross-shard
relationships are resolved by a **collective exchange**: each shard
contributes its slice of the cross-shard composites, ``lax.all_gather``
replicates them along the mesh, and each shard runs ONE divisibility
scan of its local rows plus every gathered cross row against its own
query primes.  A cross
composite's hits in a shard's mask are exactly the member primes that
shard owns, so no query ever leaves its owner shard.  The host copies
each shard's hit coordinates, and a shard's mask only when its hits
overflow their fixed slots.

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

from repro.kernels.ops import _bucket, prime_lanes
from repro.obs import profile
from repro.obs.profile import kernel_scope
from repro.sharding.stripes import BlockStripes

from ..primes import CacheLevel, LEVEL_PRIME_RANGES
from .tables import keyed_queries, rows_from_hits

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
    (:func:`repro.kernels.ops._bucket`)."""
    width = _bucket(max([r.shape[0] for r in rows] + [1]), mult)
    out = np.full((len(rows), width), fill, dtype=dtype)
    for i, r in enumerate(rows):
        out[i, :r.shape[0]] = r
    return out


def _pad_digit_stack(rows: Sequence[np.ndarray], mult: int, L: int,
                     width: Optional[int] = None) -> np.ndarray:
    """Stack ragged (n_i, L) digit matrices into (S, W, L); pad rows
    encode the value 1 (match nothing) and W is bucketed to
    ``mult * 2**k`` like :func:`_pad_rows`."""
    if width is None:
        width = _bucket(max([r.shape[0] for r in rows] + [1]), mult)
    out = np.zeros((len(rows), width, L), dtype=np.int32)
    out[:, :, 0] = 1
    for i, r in enumerate(rows):
        if r.shape[0]:
            out[i, :r.shape[0], :] = r
    return out


@dataclass
class ShardScanReport:
    """Per-refresh work split (benchmark / introspection output).
    ``cross_rows`` is the gathered cross slice every shard scans after
    its local rows (padded, ``n_shards`` times the per-shard width)."""

    n_shards: int = 0
    used_shard_map: bool = False
    local_composites: List[int] = field(default_factory=list)
    cross_composites: int = 0
    queries_per_shard: List[int] = field(default_factory=list)
    cross_rows: int = 0


#: the divisibility kernel's row tile; a shard's local plus gathered rows
#: are padded to a multiple of it
_ROW_BLOCK = 256


def _one_shard_scan(lc, qs, gathered, *, interpret: bool):
    """One shard's kernel work: the divisibility scan of its local rows
    ``lc`` (C, L), then every shard's gathered cross rows ``gathered``
    (X, L), against its owned query primes ``qs`` (Q,), with the hits
    compacted on the device in the same program
    (:func:`~repro.kernels.factorize.divisibility_hits_pallas`).

    Returns ``(hits, mask)``: the ``[total, per-query counts (Q), rows
    (HITS_PER_ROW * Q)]`` vector and the ``(Q, R)`` query-major mask, R
    being C + X rounded up to the kernel's row tile.  Pad rows encode
    the value 1 and match nothing."""
    import jax.numpy as jnp

    from repro.kernels.factorize import divisibility_hits_pallas

    rows = jnp.concatenate([lc, gathered])
    pad = (-rows.shape[0]) % _ROW_BLOCK
    if pad:
        one = (jnp.arange(rows.shape[1]) == 0).astype(rows.dtype)
        rows = jnp.concatenate(
            [rows, jnp.broadcast_to(one, (pad, rows.shape[1]))])
    return divisibility_hits_pallas(rows, qs, block_n=_ROW_BLOCK,
                                    interpret=interpret)


@functools.lru_cache(maxsize=16)
def _shard_map_scan(mesh, interpret: bool):
    """The shard_map scan over ``mesh``, memoized per mesh; ``jit`` keeps
    one executable per bucketed input shape.  Each shard's hit vector
    and mask are laid end to end along the leading axis: ``(S * H,)``
    and ``(S * Q, R)``."""
    import jax

    from repro.sharding.partition import shard_stack_spec

    axes = tuple(mesh.axis_names)
    spec = shard_stack_spec(mesh)       # leading shard axis over data x model

    def body(lc, qs, xc):
        gathered = jax.lax.all_gather(xc[0], axes, tiled=True)
        return _one_shard_scan(lc[0], qs[0], gathered, interpret=interpret)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(spec,) * 3,
                                 out_specs=(spec, spec), check_vma=False))


def _scan_sharded(local_c: np.ndarray, queries: np.ndarray,
                  cross_c: np.ndarray, mesh, **counts,
                  ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """The per-shard kernel work: one compacting divisibility scan a
    shard.

    Inputs are padded stacks: (S, C, L) local digit rows, (S, Q) query
    primes and (S, X/S, L) cross slices.  Returns, a shard, the hit
    count of each of its Q queries and the mask row of each hit in
    (query, row) order, rows ``[0, C)`` local and ``[C, C + X)`` the
    gathered cross rows in slice order.  With a mesh of exactly S
    devices the work runs under ``shard_map`` (one shard per device, the
    cross slices replicated by ``lax.all_gather`` — the collective
    exchange); otherwise a host loop runs the identical program per
    shard.

    The host copies each shard's hit vector, not its mask.  A shard
    whose hits overflow their ``HITS_PER_ROW * Q`` slots copies its own
    mask as well and compacts it here, so the answer is exact either
    way.  ``counts`` go to the span, with ``hits`` (the masks' set
    cells) and ``dense`` (the shards that overflowed).
    """
    import jax.numpy as jnp

    from repro.kernels.ops import _scan_hits, check_32bit, interpret_mode

    interpret = interpret_mode()
    check_32bit()
    S, C, L = local_c.shape
    Q = queries.shape[1]
    X = S * cross_c.shape[1]
    with kernel_scope("sharded_exchange", items=S * (C + X), shards=S,
                      c=C, q=Q, **counts):
        if mesh is not None and mesh.size == S:
            hits, mask = _shard_map_scan(mesh, interpret)(
                jnp.asarray(local_c), jnp.asarray(queries),
                jnp.asarray(cross_c))
            # each device's own (Q, R) mask, copied only on overflow
            masks = [sh.data for sh in sorted(
                mask.addressable_shards,
                key=lambda sh: sh.index[0].start or 0)]
        else:                           # host loop, same program, same math
            gathered = jnp.asarray(cross_c.reshape(-1, L))
            hits, masks = zip(*[
                _one_shard_scan(jnp.asarray(local_c[s]),
                                jnp.asarray(queries[s]), gathered,
                                interpret=interpret)
                for s in range(S)])
            hits = jnp.concatenate(hits)
        with profile.span("kernels.sharded_exchange.fetch"):
            found = [_scan_hits(h, m, Q) for h, m in
                     zip(np.asarray(hits).reshape(S, -1), masks)]
        profile.count(hits=sum(len(rows) for _, rows, _ in found),
                      dense=sum(dense for *_, dense in found))
        return [(per_query, rows) for per_query, rows, _ in found]


def sharded_successor_table(registry, assigner, data_ids: Sequence[int],
                            slices, mesh=None,
                            report: Optional[ShardScanReport] = None,
                            ) -> Dict[int, List[int]]:
    """Mesh-partitioned twin of :func:`repro.core.engine.successor_table`.

    Produces BIT-IDENTICAL rows (same candidates, same order — global
    registry order, deduplicated by relationship, expanded in
    ``rel.primes`` order, by the shared
    :func:`~repro.core.engine.tables.rows_from_hits`) while splitting the
    scan work by prime ownership: each shard's Pallas divisibility scan
    covers its local registry slice and the gathered cross-shard slice,
    against only the query primes it owns.

    ``slices`` is the registry split, a
    :class:`repro.sharding.reshard.ShardSlices`; it is brought up to the
    registry's version here, and its partition routes the queries.
    """
    partition = slices.partition
    S = partition.n_shards
    keyed = keyed_queries(assigner, data_ids)
    if registry.n_composites == 0 or not keyed:
        return {d: [] for d, _ in keyed}

    with profile.span("tables.pack", queries=len(keyed),
                      composites=registry.n_composites):
        # ---- partition state: registry slices and query routing --------- #
        slices.sync(registry)
        local_pos, cross_pos = slices.local(), slices.cross()
        by_shard: List[List[Tuple[int, int]]] = [[] for _ in range(S)]
        for d, p in keyed:
            by_shard[partition.owner(p)].append((d, p))

        queries = _pad_rows([prime_lanes([p for _, p in sh])
                             for sh in by_shard], 512, 0)
        # per-shard cross-slice width bucketed to powers of two, like every
        # other stack: an exact ceil(cross/S) width would change the compiled
        # shard_map shape on nearly every registry growth
        per = _bucket(-(-max(len(cross_pos), 1) // S), 8)

        digits = registry.limbs_array()
        L = registry.n_limbs
        local_c = _pad_digit_stack(
            [digits[np.asarray(pos, dtype=np.int64)]
             if pos else np.empty((0, L), np.int32)
             for pos in local_pos], 256, L)
        cross_digits = (digits[np.asarray(cross_pos, dtype=np.int64)]
                        if cross_pos else np.empty((0, L), np.int32))
        cross_sh = _pad_digit_stack(
            [cross_digits[s * per:(s + 1) * per] for s in range(S)],
            1, L, width=per)

    # ---- kernel work (shard_map when the mesh matches) ------------------ #
    n_local = [len(p) for p in local_pos]
    found = _scan_sharded(local_c, queries, cross_sh, mesh,
                          cross=len(cross_pos), gathered=S * per,
                          local_max=max(n_local), local_min=min(n_local),
                          q_max=max(len(sh) for sh in by_shard))

    if report is not None:
        report.n_shards = S
        report.used_shard_map = mesh is not None and mesh.size == S
        report.local_composites = n_local
        report.cross_composites = len(cross_pos)
        report.queries_per_shard = [len(sh) for sh in by_shard]
        report.cross_rows = S * per

    with profile.span("tables.candidates"):
        # ---- each owned query's hits, as registry positions: local rows
        # first, then the gathered cross rows (cross index == gathered
        # row, the slices being consecutive); padded queries hit nothing
        C = local_c.shape[1]
        pos, per_query = [], []
        for s, (counts, rows) in enumerate(found):
            row_pos = np.full(C + len(cross_pos), -1, np.int64)
            row_pos[:n_local[s]] = local_pos[s]
            row_pos[C:] = cross_pos
            pos.append(row_pos[rows])
            per_query.append(counts[:len(by_shard[s])])
        pos, per_query = np.concatenate(pos), np.concatenate(per_query)
        # local and cross rows interleave in registry order
        order = np.lexsort((pos, np.repeat(np.arange(per_query.size),
                                           per_query)))
        hits_of = np.split(pos[order], np.cumsum(per_query)[:-1])

    with profile.span("tables.rows"):
        return rows_from_hits(registry, assigner,
                              [q for sh in by_shard for q in sh], hits_of)
