"""Plain reference of a continuous-batching slot scheduler over a paged
KV cache with successor prefetch.  Imports nothing of the program.

Semantics, as the configuration states them:

* Pages hold ``page_size`` tokens and are content-addressed by the whole
  prefix they end: two prompts share a page exactly when they share every
  token up to its end.  Page ids are handed out 0, 1, 2, ... in order of
  first appearance.  Here a page is keyed by (id of the page before it,
  its own tokens), which identifies the same prefix.
* Consecutive pages of a prompt form a chain edge.  A page's successor
  row lists the other end of every distinct edge through it, in the order
  the edges were first registered.
* HBM holds ``hbm_pages`` pages in least-recently-used order; an evicted
  page goes to the host tier.  A touch is an ``hbm`` hit, a ``host`` hit or
  ``new``, moves the page to the most recent end, and then prefetches up
  to ``prefetch_budget`` pages of its successor row that are not in HBM.
* Each tick: arrivals join a FIFO queue; free slots, lowest first, take
  the queue's head and register its prompt; slots already decoding touch
  the last ``reread_window`` pages of their chain and emit one token; one
  budget of ``prefill_tokens`` prompt tokens is spread over prefilling
  slots in slot order, touching each page a chunk reaches; a prompt that
  is fully prefilled decodes from the next tick; a request that has all
  its tokens completes and frees its slot.  Decode touches come before
  prefill touches in the tick's one batch.
* Token ``i`` of request ``r`` is ``(r * 7919 + i * 104729) % 32000``.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Sequence, Tuple

__all__ = ["ReferencePages", "ReferenceSlots", "stub_tokens"]


def stub_tokens(req_id: int, n: int) -> List[int]:
    return [(req_id * 7919 + i * 104_729) % 32_000 for i in range(n)]


class ReferencePages:
    def __init__(self, hbm_pages: int, page_size: int, prefetch_budget: int):
        self.capacity = hbm_pages
        self.page_size = page_size
        self.budget = prefetch_budget
        self.ids: Dict[Tuple[int, Tuple[int, ...]], int] = {}
        self.rows: List[List[int]] = []
        self.edges: set = set()
        self.hbm: "OrderedDict[int, bool]" = OrderedDict()
        self.host: set = set()
        self.chains: Dict[int, List[int]] = {}
        self.prefetch_log: List[Tuple[int, int]] = []
        self.counters = dict(hbm_hits=0, host_hits=0, misses=0,
                             prefetches=0, prefetch_hits=0, evictions=0,
                             shared_prefix_pages=0)

    def register(self, req_id: int, tokens: Sequence[int]) -> List[int]:
        pages: List[int] = []
        parent = -1
        for lo in range(0, len(tokens), self.page_size):
            key = (parent, tuple(tokens[lo:lo + self.page_size]))
            pid = self.ids.get(key)
            if pid is None:
                pid = len(self.rows)
                self.ids[key] = pid
                self.rows.append([])
            else:
                self.counters["shared_prefix_pages"] += 1
            pages.append(pid)
            parent = pid
        for a, b in zip(pages, pages[1:]):
            edge = (min(a, b), max(a, b))
            if a != b and edge not in self.edges:
                self.edges.add(edge)
                self.rows[a].append(b)
                self.rows[b].append(a)
        self.chains[req_id] = pages
        return pages

    def release(self, req_id: int) -> None:
        self.chains.pop(req_id, None)

    def _insert(self, pid: int, prefetched: bool) -> None:
        self.host.discard(pid)
        self.hbm[pid] = prefetched
        self.hbm.move_to_end(pid)
        while len(self.hbm) > self.capacity:
            victim, _ = self.hbm.popitem(last=False)
            self.host.add(victim)
            self.counters["evictions"] += 1

    def touch(self, req_id: int, page_idx: int) -> str:
        pid = self.chains[req_id][page_idx]
        c = self.counters
        if pid in self.hbm:
            if self.hbm[pid]:
                c["prefetch_hits"] += 1
            self.hbm[pid] = False
            self.hbm.move_to_end(pid)
            c["hbm_hits"] += 1
            tier = "hbm"
        elif pid in self.host:
            c["host_hits"] += 1
            self._insert(pid, False)
            tier = "host"
        else:
            c["misses"] += 1
            self._insert(pid, False)
            tier = "new"
        budget = self.budget
        for succ in self.rows[pid]:
            if budget <= 0:
                break
            if succ in self.hbm:
                continue
            self._insert(succ, True)
            c["prefetches"] += 1
            self.prefetch_log.append((pid, succ))
            budget -= 1
        return tier


class ReferenceSlots:
    """Replays submissions (request id, prompt, output length, arrival
    tick) for a number of ticks and records everything the scheduler and
    the cache answer."""

    def __init__(self, *, max_batch: int, page_size: int, hbm_pages: int,
                 prefetch_budget: int, reread_window: int,
                 prefill_tokens: int):
        self.pages = ReferencePages(hbm_pages, page_size, prefetch_budget)
        self.max_batch = max_batch
        self.page_size = page_size
        self.window = reread_window
        self.prefill_tokens = prefill_tokens
        self.slots: List[dict] = [None] * max_batch
        self.waiting: List[dict] = []
        self.touches: List[Tuple[int, int, str]] = []
        self.requests: Dict[int, dict] = {}

    def run(self, submissions: Sequence[Tuple[int, Sequence[int], int, int]],
            ticks: int) -> None:
        pending = sorted(submissions, key=lambda s: (s[3], s[0]))
        nxt = 0
        for t in range(ticks):
            while nxt < len(pending) and pending[nxt][3] <= t:
                rid, prompt, max_new, _ = pending[nxt]
                req = dict(id=rid, prompt=prompt, need_new=max_new,
                           prefilled=0, gen=0, first=None, done=None,
                           decoding=False)
                self.requests[rid] = req
                self.waiting.append(req)
                nxt += 1
            self._tick(t)

    def _tick(self, t: int) -> None:
        fresh = set()
        for i in range(self.max_batch):
            if self.slots[i] is None and self.waiting:
                req = self.waiting.pop(0)
                self.slots[i] = req
                fresh.add(i)
                self.pages.register(req["id"], req["prompt"])
        decoding = [i for i in range(self.max_batch)
                    if self.slots[i] is not None
                    and self.slots[i]["decoding"] and i not in fresh]
        items: List[Tuple[int, int]] = []
        for i in decoding:
            req = self.slots[i]
            n = len(self.pages.chains[req["id"]])
            items.extend((req["id"], j)
                         for j in range(max(0, n - self.window), n))
        budget = self.prefill_tokens
        ps = self.page_size
        for i in range(self.max_batch):
            req = self.slots[i]
            if req is None or req["decoding"]:
                continue
            old = req["prefilled"]
            new = old + min(budget, len(req["prompt"]) - old)
            budget -= new - old
            items.extend((req["id"], j)
                         for j in range(-(-old // ps), -(-new // ps)))
            req["prefilled"] = new
            if new >= len(req["prompt"]):
                req["decoding"] = True
        for rid, j in items:
            self.touches.append((rid, j, self.pages.touch(rid, j)))
        for i in decoding:
            req = self.slots[i]
            if req["gen"] == 0:
                req["first"] = t
            req["gen"] += 1
            if req["gen"] >= req["need_new"]:
                req["done"] = t
                self.pages.release(req["id"])
                self.slots[i] = None

    def tokens_of(self, rid: int) -> List[int]:
        req = self.requests[rid]
        return stub_tokens(rid, req["gen"]) if req["done"] is not None \
            else []
