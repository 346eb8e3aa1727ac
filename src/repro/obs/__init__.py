"""PFCS observability layer: event tracing, serving telemetry, kernel
profiling and program spans (DESIGN.md §13) — disabled by default,
provably inert when off.

Every serving cache, slot front-end, and engine carries an ``obs``
attribute that defaults to ``None``; every hook in the hot paths is
guarded by ``if self.obs is not None``.  Attaching an
:class:`Observability` turns on event recording and telemetry
snapshots without touching a single placement decision — the
tracing-off parity sweep in ``tests/test_obs.py`` pins that the
counters, tier logs, LRU orders, and prefetch logs of every backend
are bit-identical with ``obs=None``, with a zero-capacity tracer, and
with a live tracer attached.

Documented with runnable examples in docs/api.md:
:class:`~repro.obs.Observability` (the façade),
:class:`~repro.obs.trace.EventTracer` (the int32 event ring),
:func:`~repro.obs.trace.trace_diff` (the differential-trace axis),
:class:`~repro.obs.telemetry.Telemetry` (gauges + histograms),
:class:`~repro.obs.telemetry.Progress` (host-side rate reporting),
:func:`~repro.obs.profile.kernel_scope` (named-scope + launch-ledger
profiling), and :func:`~repro.obs.profile.span` (the span recorder).
"""

from __future__ import annotations

import json
from typing import Optional

from . import profile
from .telemetry import Progress, StreamingHist, Telemetry
from .trace import (EVENT_FIELDS, EVENT_NAMES, EV_ADMIT, EV_AGE_OUT,
                    EV_COMPLETE, EV_COW, EV_DEDUP_HIT, EV_DEDUP_PROMOTE,
                    EV_EVICT, EV_GCD_EXCHANGE, EV_PREEMPT, EV_PREFETCH,
                    EV_PREFILL_CHUNK, EV_RECOVERY, EV_RESUME_PREFETCH,
                    EventTracer, TraceEvent, trace_diff)

__all__ = [
    "Observability", "EventTracer", "TraceEvent", "trace_diff",
    "Telemetry", "StreamingHist", "Progress", "profile",
    "EVENT_FIELDS", "EVENT_NAMES",
    "EV_ADMIT", "EV_PREFILL_CHUNK", "EV_PREEMPT", "EV_RESUME_PREFETCH",
    "EV_COMPLETE", "EV_EVICT", "EV_PREFETCH", "EV_DEDUP_HIT",
    "EV_DEDUP_PROMOTE", "EV_COW", "EV_AGE_OUT", "EV_GCD_EXCHANGE",
    "EV_RECOVERY",
]


class Observability:
    """The attachable observability façade: one event tracer + one
    telemetry sink, carried by caches / slot machines / engines as
    their ``obs`` attribute.

    ``trace_capacity=0`` keeps the tracer attached but recording
    nothing (pure counter bumps); ``telemetry=False`` drops the
    telemetry sink entirely.  The kernel profiling ledger and the span
    recorder are process-global (``repro.obs.profile``) and merely
    *reported* here: the export carries the ledger and the spans the
    recorder kept in memory.
    """

    def __init__(self, trace_capacity: int = 4096,
                 telemetry: bool = True,
                 telemetry_capacity: int = 4096):
        self.trace = EventTracer(trace_capacity)
        self.telemetry: Optional[Telemetry] = (
            Telemetry(telemetry_capacity) if telemetry else None)

    # hot-path hook: one guarded call in the instrumented sites
    def emit(self, kind: int, **lanes) -> None:
        self.trace.emit(kind, **lanes)

    def export(self) -> dict:
        """Everything observed, as one JSON-ready payload (the input
        format of ``tools/trace_view.py``)."""
        return {
            "schema": {str(k): v for k, v in EVENT_NAMES.items()},
            "trace": self.trace.export(),
            "telemetry": (self.telemetry.export()
                          if self.telemetry is not None else None),
            "kernel_launches": profile.summary(),
            "spans": [r.as_dict() for r in profile.spans()],
        }

    def export_json(self, path) -> None:
        with open(path, "w") as fh:
            json.dump(self.export(), fh, indent=1, sort_keys=True)
            fh.write("\n")


def attach(target, obs: Optional[Observability]) -> Optional[Observability]:
    """Attach ``obs`` to an engine / slot front-end and its cache
    tiers (``pages`` and, when present, ``experts``).  Returns ``obs``
    for chaining; ``attach(target, None)`` detaches."""
    target.obs = obs
    for attr in ("pages", "experts"):
        tier = getattr(target, attr, None)
        if tier is not None:
            tier.obs = obs
    return obs
