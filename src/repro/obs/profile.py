"""Program spans, kernel scopes and a launch ledger (DESIGN.md §13.4).

**Spans.**  :func:`span` marks one region of the program by name::

    with profile.span("pages.refresh", pages=n):
        ...

Off (the default), ``span`` is one module-level boolean check that
returns a shared null context: no clock read, no allocation.  On
(:func:`record` / :func:`recording`), each span reads ``start`` and
``end`` on the recorder's clock (``time.perf_counter`` unless a caller
installs another), records the id of the span open around it and the
counts given, and goes to the recorder's sink, or to an in-memory list
(:func:`spans`) when no sink is installed.  With ``annotate=True`` each
span is also a ``jax.profiler.TraceAnnotation``, so a profiler trace
carries it on the device's clock.  :func:`count` adds counts to the
innermost open span, for numbers known only part-way through it.

**Kernel scopes.**  Every Pallas launch wrapper in ``repro.kernels.ops``
(and the shard_map exchange in ``repro.core.engine.shard``) runs its
body under :func:`kernel_scope`, which

  * always annotates the region with ``jax.named_scope`` — a metadata
    tag visible to ``jax.profiler`` traces and XLA HLO dumps, with zero
    numeric effect;
  * while spans are recorded, is the span ``kernels.<name>``;
  * while the ledger is **enabled** (:func:`enable`, off by default),
    accumulates a per-name launch ledger ``{calls, items, wall_s}``,
    ``wall_s`` from the same pair of clock reads as the span.

Both are process-global on purpose: spans and kernel launches happen
deep under cache internals where threading a handle through every call
would be pure noise, and their times are only ever *reported* (spans
into a trace or a benchmark's per-layer metrics, the ledger into the
wall-clock-exempt ``obs`` block of ``BENCH_*.json``), never gated.
"""

from __future__ import annotations

import itertools
import time
from contextlib import contextmanager, nullcontext
from typing import Callable, Dict, List, Optional

import jax

__all__ = ["span", "count", "record", "recording", "recorded", "spans",
           "clear_spans", "SpanRecord", "kernel_scope", "enable", "enabled",
           "reset", "summary", "profiling"]

# -- the span recorder ------------------------------------------------------ #

_recording = False
_clock: Callable[[], float] = time.perf_counter
_annotate = False
_records: List["SpanRecord"] = []
_sink: Callable[["SpanRecord"], None] = _records.append
_open: List["SpanRecord"] = []
_ids = itertools.count()
_NULL = nullcontext()

# -- the launch ledger ------------------------------------------------------ #

_enabled = False
_ledger: Dict[str, Dict[str, float]] = {}


class SpanRecord:
    """One recorded span: ``id``, ``parent`` (the enclosing span's id, or
    None), ``name``, ``start`` and ``end`` on the recorder's clock, and
    ``counts``."""

    __slots__ = ("id", "parent", "name", "start", "end", "counts")

    def __init__(self, name: str, counts: Dict[str, int]):
        self.id = next(_ids)
        self.parent: Optional[int] = None
        self.name = name
        self.start = 0.0
        self.end = 0.0
        self.counts = counts

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def as_dict(self) -> dict:
        return {"id": self.id, "parent": self.parent, "name": self.name,
                "start": self.start, "end": self.end,
                "counts": dict(self.counts)}

    def __repr__(self) -> str:
        return (f"SpanRecord({self.name!r}, id={self.id}, "
                f"parent={self.parent}, {self.seconds:.6f} s, "
                f"{self.counts})")


class _Span:
    """An open span while the recorder is on."""

    __slots__ = ("rec", "_ann")

    def __init__(self, name: str, counts: Dict[str, int]):
        self.rec = SpanRecord(name, counts)
        self._ann = None

    def __enter__(self) -> SpanRecord:
        rec = self.rec
        if _annotate:
            self._ann = jax.profiler.TraceAnnotation(rec.name)
            self._ann.__enter__()
        if _open:
            rec.parent = _open[-1].id
        _open.append(rec)
        rec.start = _clock()
        return rec

    def __exit__(self, *exc) -> bool:
        rec = self.rec
        rec.end = _clock()
        _open.pop()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        _sink(rec)
        return False


def span(name: str, **counts):
    """A named region: a shared null context while the recorder is off,
    else a context that records one :class:`SpanRecord` (and yields it)."""
    if not _recording:
        return _NULL
    return _Span(name, counts)


def count(**counts) -> None:
    """Add counts to the innermost open span (nothing while off)."""
    if _recording and _open:
        _open[-1].counts.update(counts)


def record(on: bool = True, *, clock: Optional[Callable[[], float]] = None,
           sink: Optional[Callable[[SpanRecord], None]] = None,
           annotate: bool = False) -> None:
    """Turn the span recorder on or off.  ``clock`` defaults to
    ``time.perf_counter``; ``sink`` receives each finished span (default:
    the in-memory list :func:`spans` returns); ``annotate`` also opens a
    ``jax.profiler.TraceAnnotation`` per span."""
    global _recording, _clock, _sink, _annotate
    _recording = bool(on)
    _clock = clock if clock is not None else time.perf_counter
    _sink = sink if sink is not None else _records.append
    _annotate = bool(annotate) and _recording


def recorded() -> bool:
    return _recording


@contextmanager
def recording(*, clock: Optional[Callable[[], float]] = None,
              sink: Optional[Callable[[SpanRecord], None]] = None,
              annotate: bool = False):
    """Scoped :func:`record`: the kept list is cleared, spans are recorded
    for the duration, and the recorder's previous state is restored.
    Yields the kept list (empty when a ``sink`` takes the spans)."""
    global _recording, _clock, _sink, _annotate
    prev = (_recording, _clock, _sink, _annotate)
    clear_spans()
    record(True, clock=clock, sink=sink, annotate=annotate)
    try:
        yield _records
    finally:
        _recording, _clock, _sink, _annotate = prev


def spans() -> List[SpanRecord]:
    """The spans kept in memory, in the order they ended."""
    return list(_records)


def clear_spans() -> None:
    _records.clear()


# -- kernel scopes and the launch ledger ------------------------------------ #

def enable(on: bool = True) -> None:
    """Turn the launch ledger on/off (named scopes are always applied)."""
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


def reset() -> None:
    """Drop all accumulated ledger entries."""
    _ledger.clear()


def summary() -> Dict[str, Dict[str, float]]:
    """Per-kernel launch ledger: ``{name: {calls, items, wall_s}}``."""
    return {name: dict(rec) for name, rec in sorted(_ledger.items())}


@contextmanager
def kernel_scope(name: str, items: int = 0, **counts):
    """Annotate (always) one kernel launch; while recorded, the span
    ``kernels.<name>``; while the ledger is enabled, one ledger entry.

    ``items`` is the batch size the launch processed (composites,
    query primes, gcd pairs, ...) so the ledger can report per-item
    rates alongside raw walls; ``counts`` go to the span only.
    """
    with jax.named_scope(name):
        if not (_enabled or _recording):
            yield
            return
        sp = (_Span("kernels." + name, dict(counts, items=int(items)))
              if _recording else None)
        t0 = sp.__enter__().start if sp is not None else _clock()
        try:
            yield
        finally:
            if sp is not None:
                sp.__exit__(None, None, None)
                t1 = sp.rec.end
            else:
                t1 = _clock()
            if _enabled:
                rec = _ledger.setdefault(
                    name, {"calls": 0, "items": 0, "wall_s": 0.0})
                rec["calls"] += 1
                rec["items"] += int(items)
                rec["wall_s"] += t1 - t0


@contextmanager
def profiling():
    """Scoped enable: ledger is reset and collected for the duration.

    Yields the live ledger dict so callers can snapshot it on exit::

        with profiling():
            run_benchmark()
            obs_block = {"kernel_launches": summary()}
    """
    prev = _enabled
    reset()
    enable(True)
    try:
        yield _ledger
    finally:
        enable(prev)
