"""Drives the null-model ``SlotMachine`` page manager and checks it
against the plain reference (``references/slot_pages.py``).

The window calls ``SlotMachine.step``: one tick admits, prefills, touches
one bulk batch of pages through the PFCS page cache and emits one stub
token per decoding slot.  With ``discover="kernel"`` every registry change
is followed by a whole successor-table rebuild through the Pallas
``divisibility_scan`` and ``factorize_batch`` kernels at the next touch.

Set-up fills the slots with the table kept at registration
(``discover="incremental"``, which builds the same rows), then turns to
the configured refresh path and compiles the kernel shapes the window
can reach.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from chipbench.references.slot_pages import ReferenceSlots, stub_tokens

__all__ = ["Session"]

_ENGINE_KEYS = ("max_batch", "page_size", "hbm_pages", "prefetch_budget",
                "reread_window", "prefill_tokens", "max_bits")
_PARITY = ("hbm_hits", "host_hits", "misses", "prefetches", "prefetch_hits",
           "evictions", "shared_prefix_pages")
#: the control refreshes the successor table on this share of ticks only
_CONTROL_REFRESH_EVERY = 8
#: set-up gives up when the slots are not all filled after this many ticks
_FILL_TICKS = 100_000


class Session:
    """One run of one cell: the program, its traffic and its records."""

    def __init__(self, config: Dict, traffic, clock=time.perf_counter,
                 control: bool = False):
        from repro.serving.slots import SlotMachine

        self.config = config
        self.traffic = traffic
        self.clock = clock
        self.machine = SlotMachine(kv=config["kv"],
                                   **{k: config[k] for k in _ENGINE_KEYS})
        self.pages = self.machine.pages
        self.submitted: List[Tuple[int, List[int], int, int]] = []
        self.due: List[float] = []
        self.touches: List[Tuple[int, int, str]] = []
        self.tick_end: List[float] = []
        self.t0: Optional[float] = None
        self.launches: List[Tuple[str, int, int, int]] = []
        self._next = 0
        self._queue: List = []
        self._ticks_at_open = 0
        self._scans_at_open = 0
        self._record_touches()
        if control:
            self._defer_refresh()

    # -- recording ------------------------------------------------------ #

    def _record_touches(self) -> None:
        pages, touches = self.pages, self.touches
        touch_batch = pages.touch_batch

        def recorded(items):
            tiers = touch_batch(items)
            touches.extend((r, i, t) for (r, i), t in zip(items, tiers))
            return tiers

        pages.touch_batch = recorded

    def _defer_refresh(self) -> None:
        pages = self.pages
        sync = pages._sync_tables
        calls = [0]

        def deferred():
            calls[0] += 1
            if calls[0] % _CONTROL_REFRESH_EVERY == 0:
                sync()

        pages._sync_tables = deferred

    def instrument(self, spans) -> None:
        """Time the cache's bound methods and record each kernel launch's
        logical shape (traced runs only)."""
        import repro.kernels.ops as ops

        pages = self.pages
        pages.touch_batch = spans.wrap("touch_batch", pages.touch_batch)
        pages.refresh_tables = spans.wrap("refresh_tables",
                                          pages.refresh_tables)
        launches = self.launches
        scan, factorize = ops.divisibility_scan, ops.factorize_batch

        def scan_logged(registry, query_primes, *a, **kw):
            d = ops.as_digits(registry)
            launches.append(("divisibility_mask", d.shape[0], d.shape[1],
                             len(query_primes)))
            return scan(d, query_primes, *a, **kw)

        def factorize_logged(composites, primes, *a, **kw):
            d = ops.as_digits(composites)
            launches.append(("factorize_squarefree", d.shape[0],
                             d.shape[1], len(primes)))
            return factorize(d, primes, *a, **kw)

        ops.divisibility_scan = scan_logged
        ops.factorize_batch = factorize_logged

    # -- driving -------------------------------------------------------- #

    def _submit(self, req, due: float) -> None:
        m = self.machine
        prompt = req.prompt.tolist()
        rid = m.submit(prompt, max_new_tokens=req.max_new_tokens,
                       arrival=m.now)
        self.submitted.append((rid, prompt, req.max_new_tokens, m.now))
        self.due.append(due)

    def _submit_due(self) -> None:
        if not self._queue:
            return
        now = self.clock() - self.t0
        while self._queue and self._queue[0].due_s <= now:
            req = self._queue.pop(0)
            self._submit(req, self.t0 + req.due_s)

    def step(self) -> None:
        self._submit_due()
        self.machine.step()
        self.tick_end.append(self.clock())

    def setup(self) -> None:
        """Submit the mix's fill, run until every slot has held a decoding
        request, then turn to the configured refresh path and compile the
        kernel shapes the window can reach."""
        from repro.obs import profile
        from repro.serving.slots import PHASE_DECODE

        profile.enable(True)
        start = self.clock()
        for i in range(self.traffic.fill):
            self._submit(self.traffic.request(i), start)
        self._next = self.traffic.fill
        # make_kv_backend has no discover= argument: choose the refresh
        # path on the built cache
        self.pages.discover = "incremental"
        m = self.machine
        filled = np.zeros(m.max_batch, bool)
        for _ in range(_FILL_TICKS):
            self.step()
            filled |= m.phase == PHASE_DECODE
            if filled.all():
                break
        else:
            raise RuntimeError(f"slots not all decoding within "
                               f"{_FILL_TICKS} ticks")
        self.pages.discover = self.config["discover"]
        self._warm_buckets()

    def open_window(self, seconds: float) -> None:
        """Generate every request due before the window closes, so that
        the window spends no time making traffic; arrivals count from
        here."""
        while self.traffic.due_s(self._next) <= seconds + 5.0:
            self._queue.append(self.traffic.request(self._next))
            self._next += 1
        self._ticks_at_open = self.machine.ticks
        self._scans_at_open = self._scans()
        self.t0 = self.clock()

    def _warm_buckets(self) -> None:
        """The registry grows through the window; compile each kernel at
        the row and pool buckets it has now and at the next ones."""
        from repro.kernels.ops import divisibility_scan, factorize_batch

        reg = self.pages.registry
        n, p = len(reg.composites_view()), len(reg.primes_array())
        width = reg.n_limbs
        for rows in (n, 2 * n):
            digits = np.zeros((max(rows, 1), width), np.int32)
            digits[:, 0] = 1
            for q in (512, 1024):
                divisibility_scan(digits, [2] * q)
            for pool in (p, 2 * p):
                factorize_batch(digits, [2] * max(pool, 1))

    # -- results -------------------------------------------------------- #

    def token_times(self) -> List[np.ndarray]:
        """Per request, the host time of each of its tokens: a decoding
        slot emits one token a tick, from its first tick to its last."""
        ends = np.asarray(self.tick_end)
        last = self.machine.now - 1
        out = []
        for rid, *_ in self.submitted:
            r = self.machine.requests[rid]
            if r.first_tick is None:
                out.append(ends[:0])
                continue
            stop = r.done_tick if r.done_tick is not None else last
            out.append(ends[r.first_tick:stop + 1])
        return out

    def _program_rows(self, n_pages: int) -> List[List[int]]:
        rows = self.pages.successor_rows()
        return [rows.get(p, []) for p in range(n_pages)]

    @staticmethod
    def _scans() -> int:
        """``divisibility_scan`` launches so far, by the program's own
        ``kernel_scope`` counter."""
        from repro.obs import profile

        return profile.summary().get("divisibility_scan", {}).get("calls", 0)

    def check(self) -> Tuple[List[Tuple[str, float, str]], int]:
        """(name, reading, limit) for every number compared, a limit being
        ``== 0`` or ``>= 1``; and the count of requests whose schedule or
        tokens differ from the reference's."""
        from repro.obs import profile

        scans = self._scans() - self._scans_at_open
        profile.enable(False)
        cfg = self.config
        ref = ReferenceSlots(max_batch=cfg["max_batch"],
                             page_size=cfg["page_size"],
                             hbm_pages=cfg["hbm_pages"],
                             prefetch_budget=cfg["prefetch_budget"],
                             reread_window=cfg["reread_window"],
                             prefill_tokens=cfg["prefill_tokens"])
        ref.run(self.submitted, self.machine.ticks)

        got, want = self.touches, ref.touches
        touch_bad = abs(len(got) - len(want)) + sum(
            a != b for a, b in zip(got, want))
        got_pf, want_pf = self.pages.prefetch_log, ref.pages.prefetch_log
        prefetch_bad = abs(len(got_pf) - len(want_pf)) + sum(
            a != b for a, b in zip(got_pf, want_pf))
        stats = self.pages.stats
        counter_bad = sum(getattr(stats, k) != ref.pages.counters[k]
                          for k in _PARITY)

        request_bad = 0
        for rid, *_ in self.submitted:
            r = self.machine.requests[rid]
            w = ref.requests.get(rid, {"first": None, "done": None, "gen": 0})
            if (r.first_tick, r.done_tick) != (w["first"], w["done"]):
                request_bad += 1
            elif r.done_tick is not None and \
                    r.generated != stub_tokens(rid, w["gen"]):
                request_bad += 1

        n_pages = max(len(ref.pages.rows), self.pages._next_page)
        want_rows = ref.pages.rows + [[]] * (n_pages - len(ref.pages.rows))
        row_bad = sum(a != b for a, b in zip(self._program_rows(n_pages),
                                              want_rows))
        numbers = [("touch_mismatches", touch_bad, "== 0"),
                   ("prefetch_mismatches", prefetch_bad, "== 0"),
                   ("counter_mismatches", counter_bad, "== 0"),
                   ("request_mismatches", request_bad, "== 0"),
                   ("successor_row_mismatches", row_bad, "== 0"),
                   ("scan_launches_in_window", scans, ">= 1"),
                   ("window_ticks", self.machine.ticks - self._ticks_at_open,
                    ">= 1")]
        return numbers, request_bad

    def attempted(self) -> int:
        """Requests submitted to the program in the run."""
        return len(self.submitted)
