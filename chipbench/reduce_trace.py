"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports.

* device-busy intervals: the union of the op events on each TPU device
  plane, clipped to the harness's ``window`` span, averaged over devices;
* device time per op name, per program (``XLA Modules``) and per kernel:
  every ``custom-call`` op (a Pallas kernel) under its instruction's
  name less the ``.N`` suffix, ``%divisibility_mask.1 = ... custom-call``
  giving ``divisibility_mask``;
* idle gaps: the complement of the busy union inside the window, each
  named by the innermost harness span open at its midpoint (``none``
  where no span was open).

The harness spans are ``jax.profiler.TraceAnnotation`` events on the
host plane, which the profiler keeps on the same clock as the device;
the caller names the spans it opened, the window's being ``window``.
The core works on plain ``(name, start_ns, end_ns)`` tuples so that it
can be checked without a trace file.
"""

from __future__ import annotations

from collections import defaultdict
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = ["union", "reduce_events", "load_events", "reduce_file",
           "reduce_dir"]

_TOP = 10
_SUFFIX = re.compile(r"\.\d+$")

Event = Tuple[str, float, float]          # name, start ns, end ns


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(ev: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in ev
            if e > lo and s < hi]


def _innermost(spans: Sequence[Event], t: float) -> str:
    best: Optional[Event] = None
    for sp in spans:
        if sp[1] <= t < sp[2] and sp[0] != "window" and (
                best is None or sp[1] >= best[1]):
            best = sp
    return best[0] if best is not None else "none"


def reduce_events(devices: Dict[str, Dict[str, List[Event]]],
                  host_spans: Sequence[Event]) -> Dict:
    """``devices``: per device plane, ``{"ops": [...], "modules": [...],
    "kernels": [...]}`` events; a kernel event is named by its kernel."""
    windows = [s for s in host_spans if s[0] == "window"]
    if windows:
        lo, hi = windows[0][1], windows[0][2]
    else:
        every = [e for d in devices.values() for e in d["ops"]]
        lo = min((e[1] for e in every), default=0.0)
        hi = max((e[2] for e in every), default=0.0)
    spans = [s for s in host_spans if s[2] > lo and s[1] < hi]

    busy_ns: List[float] = []
    by_op: Dict[str, float] = defaultdict(float)
    by_program: Dict[str, float] = defaultdict(float)
    by_kernel: Dict[str, Dict] = defaultdict(
        lambda: {"seconds": 0.0, "events": 0})
    idle_by_span: Dict[str, float] = defaultdict(float)
    n_gaps = 0
    for plane in devices.values():
        ops = _clip(plane["ops"], lo, hi)
        busy = union((s, e) for _, s, e in ops)
        busy_ns.append(sum(e - s for s, e in busy))
        for name, s, e in ops:
            by_op[name] += (e - s) * 1e-9
        for name, s, e in _clip(plane.get("kernels", []), lo, hi):
            by_kernel[name]["seconds"] += (e - s) * 1e-9
            by_kernel[name]["events"] += 1
        for name, s, e in _clip(plane.get("modules", []), lo, hi):
            by_program[name] += (e - s) * 1e-9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                n_gaps += 1
                idle_by_span[_innermost(spans, (gs + ge) / 2)] += \
                    (ge - gs) * 1e-9

    n_dev = max(1, len(devices))
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy_ns) / n_dev * 1e-9

    def top(d):
        return [[k, v / n_dev] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:_TOP]]

    return {"window_s": window_s, "busy_s": busy_s, "devices": len(devices),
            "op_seconds": {k: v / n_dev for k, v in by_op.items()},
            "program_seconds": {k: v / n_dev for k, v in by_program.items()},
            "kernels": {k: {"seconds": v["seconds"] / n_dev,
                            "events": v["events"]}
                        for k, v in by_kernel.items()},
            "idle_by_span": {k: v / n_dev for k, v in idle_by_span.items()},
            "idle_gaps": n_gaps,
            "top_ops": top(by_op), "top_idle": top(idle_by_span)}


def _op_name(name: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...)`` -> ``fusion.3``: a TPU op
    event is named by its whole HLO instruction."""
    if name.startswith("%") and " = " in name:
        return name[1:name.index(" = ")]
    return name


def _kernel_name(name: str) -> Optional[str]:
    """``%factorize_squarefree.1 = (...) custom-call(...)`` ->
    ``factorize_squarefree``; None for an op that is no custom call."""
    if " custom-call(" not in name:
        return None
    return _SUFFIX.sub("", _op_name(name))


def load_events(path, span_names: Sequence[str]):
    """(devices, host spans) from one ``.xplane.pb``; host events named in
    ``span_names`` are the spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    wanted = set(span_names)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            ops, kernels = [], []
            for ev in lines["XLA Ops"].events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                ops.append((_op_name(ev.name), s, e))
                k = _kernel_name(ev.name)
                if k is not None:
                    kernels.append((k, s, e))
            mods = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in (lines["XLA Modules"].events
                               if "XLA Modules" in lines else ())]
            devices[plane.name] = {"ops": ops, "modules": mods,
                                   "kernels": kernels}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return devices, spans


def reduce_file(path, span_names: Sequence[str]) -> Dict:
    return reduce_events(*load_events(path, span_names))


def reduce_dir(trace_dir, span_names: Sequence[str]) -> Dict:
    """Reduce the newest ``.xplane.pb`` under a profiler log directory."""
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(files[-1], span_names)
