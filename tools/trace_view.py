#!/usr/bin/env python
"""Convert an ``Observability.export_json`` payload into Chrome
``trace_event`` JSON (load it in chrome://tracing or https://ui.perfetto.dev).

Mapping, in microseconds from the first recorded span:

  * program spans     -> complete events (``ph: "X"``) on pid 1
                         ("program") at their recorded start and end,
                         nested by time; their counts are the args
  * trace ring rows   -> instant events (``ph: "i"``) on pid 0
                         ("serving") at the start of their tick's
                         ``slots.step`` / ``engine.step`` span;
                         slot-scoped events land on ``tid = slot``,
                         cache/shard events on a shared "cache" track
  * telemetry gauges  -> counter events (``ph: "C"``) keyed by gauge
                         name at their tick's time

A payload without spans (the recorder was off) has no clock: its ticks
are laid out 1 ms apart instead.

Usage:
    PYTHONPATH=src python -m repro.launch.serve --trace obs.json
    python tools/trace_view.py obs.json chrome_trace.json
"""

from __future__ import annotations

import bisect
import json
import sys
from typing import Callable, Dict, List

TICK_US = 1000          # one serving tick as 1 ms, for payloads with no spans
CACHE_TID = 99          # track for events with no slot attribution
TICK_SPANS = ("slots.step", "engine.step")

_META = [
    {"ph": "M", "pid": 0, "name": "process_name",
     "args": {"name": "serving"}},
    {"ph": "M", "pid": 0, "tid": CACHE_TID, "name": "thread_name",
     "args": {"name": "cache"}},
    {"ph": "M", "pid": 1, "name": "process_name",
     "args": {"name": "program"}},
]


def _tick_clock(spans: List[dict], t0: float) -> Callable[[int], float]:
    """tick -> microseconds: the start of the tick's step span, else of
    the latest step span before it (the first one for earlier ticks)."""
    starts: Dict[int, float] = {}
    for sp in spans:
        tick = sp.get("counts", {}).get("tick")
        if sp["name"] in TICK_SPANS and tick is not None:
            starts.setdefault(int(tick), (sp["start"] - t0) * 1e6)
    if not starts:
        return lambda tick: max(int(tick), 0) * TICK_US
    ticks = sorted(starts)

    def at(tick: int) -> float:
        i = bisect.bisect_right(ticks, tick) - 1
        return starts[ticks[max(i, 0)]]

    return at


def convert(payload: dict) -> dict:
    """Observability export dict -> Chrome trace_event dict."""
    schema = {int(k): v for k, v in payload.get("schema", {}).items()}
    spans = payload.get("spans") or []
    t0 = min((sp["start"] for sp in spans), default=0.0)
    tick_us = _tick_clock(spans, t0)
    out = list(_META)

    # -- program spans -> complete events --------------------------------- #
    for sp in spans:
        out.append({
            "name": sp["name"], "ph": "X",
            "ts": (sp["start"] - t0) * 1e6,
            "dur": (sp["end"] - sp["start"]) * 1e6,
            "pid": 1, "tid": 0,
            "args": dict(sp.get("counts", {}), id=sp["id"],
                         parent=sp["parent"]),
        })

    # -- trace ring rows -> instant events -------------------------------- #
    trace = payload.get("trace") or {}
    fields = trace.get("fields") or ["kind", "tick", "slot", "req", "page",
                                     "tenant", "shard", "arg"]
    now = 0
    for seq, row in enumerate(trace.get("events", [])):
        ev = dict(zip(fields, row))
        tick = ev.get("tick", -1)
        if tick >= 0:               # untick'd events ride the last tick seen
            now = tick
        slot = ev.get("slot", -1)
        args = {k: v for k, v in ev.items()
                if k not in ("kind", "tick", "slot") and v != -1}
        args["seq"] = seq
        args["tick"] = now
        out.append({
            "name": schema.get(ev.get("kind"), f"kind{ev.get('kind')}"),
            "ph": "i", "s": "t",
            "ts": tick_us(now),
            "pid": 0,
            "tid": slot if slot >= 0 else CACHE_TID,
            "args": args,
        })

    # -- telemetry gauges -> counter events ------------------------------- #
    telem = payload.get("telemetry") or {}
    for name, ring in sorted((telem.get("gauges") or {}).items()):
        for tick, value in ring:
            out.append({
                "name": name, "ph": "C",
                "ts": tick_us(int(tick)),
                "pid": 0,
                "args": {name: value},
            })

    return {"traceEvents": out, "displayTimeUnit": "ms"}


def main(argv=None) -> dict:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        return {}
    with open(argv[0]) as fh:
        payload = json.load(fh)
    trace = convert(payload)
    if len(argv) > 1:
        with open(argv[1], "w") as fh:
            json.dump(trace, fh, indent=1)
            fh.write("\n")
        print(f"wrote {len(trace['traceEvents'])} trace events "
              f"-> {argv[1]}")
    else:
        json.dump(trace, sys.stdout, indent=1)
        print()
    return trace


if __name__ == "__main__":
    main()
