"""Drives the null-model ``SlotMachine`` over the mesh-sharded page cache
(``kv="elastic"``, one registry shard a chip) and checks it against the
same plain reference as ``slot_machine.py``.

Every registry change is followed, at the next touch, by a successor-table
rebuild through ``sharded_successor_table``: under ``shard_map`` each chip
scans its own registry slice plus the all-gathered cross-shard slice
against the query primes it owns, in one ``divisibility_mask`` launch.
The rows are bit-identical to the single-device table's, so the
reference applies unchanged.

Set-up fills the slots with the table kept at registration, restores the
backend's own bulk-rebuild mode and compiles the ``shard_map`` scan at
the buckets the window can reach.  Besides the one-chip cell's checks,
``host_loop_rebuilds`` counts window rebuilds that did not run on the
mesh.
"""

from __future__ import annotations

import time
from typing import Dict, List, Tuple

import numpy as np

from chipbench.drivers import slot_machine
from chipbench.drivers.slot_machine import _ENGINE_KEYS

__all__ = ["Session"]

#: the exchange's kernel scope, whose ledger calls count the scans
_SCOPE = "sharded_exchange"


class Session(slot_machine.Session):
    """One run of one four-chip cell."""

    def __init__(self, config: Dict, traffic, clock=time.perf_counter,
                 control: bool = False):
        from repro.core.engine.shard import ShardScanReport
        from repro.serving.slots import SlotMachine

        if not hasattr(ShardScanReport(), "cross_rows"):
            raise SystemExit("this program's sharded exchange reports no "
                             "cross_rows: its per-shard divisibility scans "
                             "cannot be counted")
        self.machine = SlotMachine(kv=config["kv"], shards=config["shards"],
                                   mesh="auto",
                                   **{k: config[k] for k in _ENGINE_KEYS})
        if self.machine.pages.mesh is None:
            raise SystemExit(f"{config['shards']} shards need as many "
                             f"devices")
        # set-up restores this, the backend's own bulk-rebuild mode,
        # after the incremental fill
        self.config = dict(config, discover=self.machine.pages.discover)
        self.traffic = traffic
        self.clock = clock
        self.pages = self.machine.pages
        self.submitted: List[Tuple[int, List[int], int, int]] = []
        self.due: List[float] = []
        self.touches: List[Tuple[int, int, str]] = []
        self.tick_end: List[float] = []
        self.t0 = None
        self.launches: List[Tuple[str, int, int, int]] = []
        self._next = 0
        self._queue: List = []
        self._ticks_at_open = 0
        self._scans_at_open = 0
        #: per rebuild, whether it ran under shard_map
        self.on_mesh: List[bool] = []
        self._rebuilds_at_open = 0
        self._record_touches()
        self._watch_rebuilds()
        if control:
            self._defer_refresh()

    # -- recording ------------------------------------------------------ #

    def _watch_rebuilds(self) -> None:
        pages, on_mesh = self.pages, self.on_mesh
        build = pages._build_rows

        def watched(discover):
            # a rebuild that bypasses the sharded scan leaves this empty
            # report, which reads as off the mesh
            pages.last_scan = type(pages.last_scan)()
            rows = build(discover)
            on_mesh.append(pages.last_scan.used_shard_map)
            return rows

        pages._build_rows = watched

    def instrument(self, spans) -> None:
        """Record the program's spans on the harness's clock (and, traced,
        in the profiler's trace), and log each rebuild's per-shard
        ``divisibility_mask`` launches with their logical sizes."""
        from repro.obs import profile

        records = spans.records
        profile.record(True, clock=spans.clock, annotate=spans.annotate,
                       sink=lambda r: records.append((r.name, r.start,
                                                      r.end)))
        pages, launches = self.pages, self.launches
        build = pages._build_rows

        def logged(discover):
            rows = build(discover)
            scan, digits = pages.last_scan, pages.registry.n_limbs
            for local, q in zip(scan.local_composites,
                                scan.queries_per_shard):
                launches.append(("divisibility_mask",
                                 local + scan.cross_composites, digits, q))
            return rows

        pages._build_rows = logged

    # -- driving -------------------------------------------------------- #

    def open_window(self, seconds: float) -> None:
        super().open_window(seconds)
        self._rebuilds_at_open = len(self.on_mesh)

    def _warm_buckets(self) -> None:
        """Rebuild once at the registry's own shapes, then compile the
        ``shard_map`` scan at the local-row, cross-slice and query buckets
        it has now and at the next ones up."""
        from repro.core.engine.shard import _pad_digit_stack, _scan_sharded
        from repro.kernels.ops import _bucket

        pages = self.pages
        pages.refresh_tables()
        scan, S, L = pages.last_scan, pages.n_shards, pages.registry.n_limbs
        local = _bucket(max(scan.local_composites), 256)
        queries = _bucket(max(scan.queries_per_shard), 512)
        per = scan.cross_rows // S
        none = [np.empty((0, L), np.int32)] * S
        for c in (local, 2 * local):
            local_c = _pad_digit_stack(none, 1, L, width=c)
            for x in (per, 2 * per):
                cross_c = _pad_digit_stack(none, 1, L, width=x)
                for q in (queries, 2 * queries):
                    _scan_sharded(local_c, np.zeros((S, q), np.int32),
                                  cross_c, pages.mesh)

    # -- results -------------------------------------------------------- #

    @staticmethod
    def _scans() -> int:
        """Exchange launches so far, by the program's ``kernel_scope``
        counter."""
        from repro.obs import profile

        return profile.summary().get(_SCOPE, {}).get("calls", 0)

    def check(self) -> Tuple[List[Tuple[str, float, str]], int]:
        """The one-chip cell's numbers, and the window's rebuilds that ran
        on the host loop instead of the mesh."""
        from repro.obs import profile

        profile.record(False)
        numbers, failed = super().check()
        window = self.on_mesh[self._rebuilds_at_open:]
        numbers.append(("host_loop_rebuilds",
                        sum(not m for m in window), "== 0"))
        return numbers, failed

