"""Vectorized batch trace-simulation engine (TPU-native simulator).

This package generalizes the jitted array-LRU fast path that used to be
the only ``lax.scan`` state machine in the simulator
(:func:`repro.core.simulator.fast_lru_hit_rate`) to the *full* system
zoo the paper compares — LRU / FIFO / 2Q / ARC / LIRS baselines and PFCS
itself — as fixed-shape array state carried through ``jax.lax.scan`` and
``jax.vmap``-batched across traces.

Design contract (see DESIGN.md §4 for the full state-layout spec):

  * **Bit-exact oracle parity.**  Every engine system reproduces the hit
    counts of its scalar oracle (``simulate_baseline`` /
    ``simulate_pfcs``) exactly — not approximately.  The scalar
    implementations stay in the tree as the cross-check oracle; the
    equivalence is enforced by ``tests/test_engine.py``.
  * **Fixed shapes.**  All per-step state is fixed-shape int32/bool
    arrays (slot arrays for bounded structures, per-key arrays for
    unbounded ones such as the LIRS recency stack), so one compiled
    ``scan`` serves any trace of the same length and any batch via
    ``vmap``.  Empty slots are ``key == -1``; recency is a monotonically
    increasing int32 micro-op counter, never a pointer structure.
  * **int32 hot path.**  Keys, timestamps, and degrees are int32
    (DESIGN.md §3); the only wider state is ARC's adaptive float64
    target ``p``, matching CPython float semantics of the oracle.
  * **Kernel-backed discovery.**  PFCS relationship discovery is a
    *precomputed table* (relationships are static during a trace — the
    registry is written at schema time), built either on the host or in
    bulk through the existing Pallas ``divisibility_scan`` /
    ``factorize_batch`` kernels (:mod:`repro.kernels.ops`).

Public entry points (documented with runnable examples in docs/api.md):

  * :func:`simulate_trace`  — one trace, one system -> AccessStats
  * :func:`simulate_batch`  — stacked traces, vmap-batched -> [AccessStats]
  * :func:`sweep`           — systems x capacity configs x traces
  * :func:`pfcs_tables`     — precomputed PFCS discovery tables
  * :func:`related_bulk`    — bulk Pallas-kernel relationship discovery
  * :func:`successor_table` — bulk chain-successor discovery (the serving
    paged-KV cache's table-refresh path, DESIGN.md §5)
  * :func:`sharded_successor_table` — the mesh-partitioned twin:
    per-shard Pallas scans under ``shard_map`` + the all-gathered
    cross-shard slice (DESIGN.md §6), with :class:`PrimeSpacePartition`
    as the ownership rule; both builders take their rows from
    ``rows_from_hits``
"""

from .batch import VECTORIZED_SYSTEMS, simulate_batch, simulate_trace, sweep
from .shard import (PrimeSpacePartition, shard_mesh,
                    sharded_successor_table)
from .tables import (PFCSTables, pfcs_tables, related_bulk,
                     rows_from_hits, successor_table)

__all__ = [
    "simulate_trace", "simulate_batch", "sweep", "VECTORIZED_SYSTEMS",
    "PFCSTables", "pfcs_tables", "related_bulk", "successor_table",
    "rows_from_hits",
    "PrimeSpacePartition", "shard_mesh", "sharded_successor_table",
]
