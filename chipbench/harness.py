"""Runs one cell once: resolve its files by name, set up, measure a
window, check the outputs, reduce the trace and print the result line.

Everything that belongs to one configuration, traffic mix, driver or
per-layer metric lives in a file of its own that is found here by the
name ``BENCHMARK.json`` gives it:

    chipbench/configs/<config>.json       sizes, source, driver, reference
    chipbench/traffic/<traffic>.json      parameters of the one generator
    chipbench/arrivals/<process>.py       ``due_s``: a mix's arrivals
    chipbench/drivers/<driver>.py         ``Session``: builds and drives
    chipbench/metrics/<metric>.py         ``read(record) -> float | None``
    chipbench/work/<kernel>.py            a kernel's logical ops and bytes

A driver's ``Session(config, traffic, clock=, control=)`` has
``setup()``; ``instrument(spans)``, traced runs only, after set-up;
``open_window(seconds)`` just before the window; ``step()``; and, after it,
``token_times()``, ``due``, ``launches`` (kernel name, then the shape
that kernel's work module counts), ``attempted()`` and ``check()``, which
returns every number compared with its limit and the count of failed
requests.
"""

from __future__ import annotations

import importlib
import json
import shutil
import statistics
import sys
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from chipbench.traffic import Traffic, load_mix

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
OUT_DIR = BENCH_DIR / "out"

__all__ = ["Cell", "resolve", "Record", "Spans", "CompileClock", "run"]


# --------------------------------------------------------------------------- #
# resolution by name                                                          #
# --------------------------------------------------------------------------- #

@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Dict
    driver: str
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _reports(metric: Dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def resolve(name: str, bench_file: Path = ROOT / "BENCHMARK.json",
            traffic_dir: Path = BENCH_DIR / "traffic") -> Cell:
    bench = json.loads(Path(bench_file).read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    mix = load_mix(Path(traffic_dir) / f"{w['traffic']}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                driver=config["driver"],
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])


def driver_module(name: str):
    return importlib.import_module(f"chipbench.drivers.{name}")


def metric_reader(name: str) -> Callable:
    return importlib.import_module(f"chipbench.metrics.{name}").read


# --------------------------------------------------------------------------- #
# spans and compile counting                                                  #
# --------------------------------------------------------------------------- #

class Spans:
    """Harness spans on the host clock; with ``annotate`` each is also a
    ``jax.profiler.TraceAnnotation``, so the trace carries it on the
    device's clock."""

    def __init__(self, clock=time.perf_counter, annotate: bool = False):
        self.clock = clock
        self.annotate = annotate
        self.records: List[Tuple[str, float, float]] = []

    @contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = self.clock()
        try:
            yield
        finally:
            t1 = self.clock()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.records.append((name, t0, t1))

    def wrap(self, name: str, fn: Callable) -> Callable:
        def timed(*a, **kw):
            with self.span(name):
                return fn(*a, **kw)
        return timed


class CompileClock:
    """Counts XLA backend compiles and sums their seconds, from JAX's
    monitoring events."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name.endswith("backend_compile_duration"):
            self.count += 1
            self.seconds += secs


# --------------------------------------------------------------------------- #
# the record metric readers read                                              #
# --------------------------------------------------------------------------- #

@dataclass
class Record:
    window: Tuple[float, float]
    token_times: List[np.ndarray]
    due: np.ndarray
    setup_s: float
    spans: List[Tuple[str, float, float]] = field(default_factory=list)
    launches: List[Tuple] = field(default_factory=list)
    trace: Optional[Dict] = None
    peaks: Optional[Dict] = None

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def in_window(self, t) -> np.ndarray:
        return (t > self.window[0]) & (t <= self.window[1])


def device_info() -> Dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def load_peaks(kind: str) -> Dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         f"chipbench/peaks.json")
    return table[kind]


def _quartile_summary(x) -> str:
    if len(x) < 2:
        return f"n={len(x)}"
    q = statistics.quantiles(x, n=4)
    return f"n={len(x)} q1={q[0]} median={q[1]} q3={q[2]}"


# --------------------------------------------------------------------------- #
# one run                                                                     #
# --------------------------------------------------------------------------- #

def run(workload: str, seed: int, seconds: float, trace: bool, *,
        started: float, require_tpu: bool = True, control: bool = False,
        prepare: Optional[Callable] = None,
        bench_file: Path = ROOT / "BENCHMARK.json",
        traffic_dir: Path = BENCH_DIR / "traffic", log=sys.stderr) -> Dict:
    """One run of one cell; returns the result line's object.  ``prepare``
    is called with the built session before set-up (tests plant faults
    through it)."""
    import jax

    cell = resolve(workload, bench_file, traffic_dir)
    dev = device_info()
    if require_tpu and (jax.default_backend() != "tpu"
                        or dev["count"] < cell.chips):
        raise SystemExit(f"{workload} needs {cell.chips} TPU chip(s); JAX "
                         f"found {dev['count']} {dev['platform']} device(s)")
    peaks = load_peaks(dev["kind"]) if require_tpu else None

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache() if require_tpu else None
    clock = time.perf_counter
    compiles = CompileClock()
    traffic = Traffic(cell.mix, seed)
    session = driver_module(cell.driver).Session(cell.config, traffic,
                                                 clock=clock,
                                                 control=control)
    spans = Spans(clock, annotate=trace)
    if prepare is not None:
        prepare(session)

    session.setup()
    if trace:
        # after set-up, so that every program is traced from the same call
        # path as in an untraced run and found in the compile cache
        session.instrument(spans)
    compiles_before = compiles.count
    trace_dir = OUT_DIR / f"trace_{workload}"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir),
                                 profiler_options=trace_options())
    session.open_window(seconds)
    t_open = clock()
    setup_s = t_open - started
    with spans.span("window"):
        while clock() - t_open < seconds:
            with spans.span("engine_step"):
                session.step()
        t_close = clock()
    if trace:
        jax.profiler.stop_trace()
    in_window_compiles = compiles.count - compiles_before
    peak = memory_peak_bytes() if require_tpu else 0

    record = Record(window=(t_open, t_close),
                    token_times=session.token_times(),
                    due=np.asarray(session.due), setup_s=setup_s,
                    spans=spans.records, launches=session.launches,
                    peaks=peaks)
    print(f"compile cache: {cache_dir}", file=log)
    print(f"set-up: {setup_s} s, {compiles_before} backend compiles "
          f"({compiles.seconds} s)", file=log)
    print(f"compiles inside the window: {in_window_compiles}", file=log)

    if trace:
        from chipbench import reduce_trace
        record.trace = reduce_trace.reduce_dir(
            trace_dir, {n for n, *_ in spans.records})

    numbers, failed = session.check()
    correct = all(_within(v, lim) for _, v, lim in numbers)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        if m["name"] == "setup_s":
            value = setup_s
        else:
            value = metric_reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    waits = [t[0] - d for t, d in zip(record.token_times, record.due)
             if len(t) and record.in_window(t[0])]
    print(f"requests with a first token in the window: {len(waits)}, "
          f"waits since due {_quartile_summary(waits)} s", file=log)
    for line in _kernel_work_lines(record):
        print(line, file=log)

    device = dict(dev, memory_peak_bytes=peak)
    if trace and record.trace is not None:
        device["busy_s"] = record.trace["busy_s"]
        device["window_s"] = record.trace["window_s"]
    out = {"correct": bool(correct),
           "attempted": session.attempted(),
           "failed": int(failed),
           "metrics": metrics,
           "device": device}
    if trace and record.trace is not None:
        out["breakdown"] = {"device_ops": record.trace["top_ops"],
                            "idle_gaps": record.trace["top_idle"]}
    out["checked"] = {n: {"value": v, "limit": lim} for n, v, lim in numbers}
    for n, v, lim in numbers:
        print(f"check {n}: {v} (limit {lim})", file=log)
    return out


def trace_options():
    """Device ops and the harness's annotations; no Python call tracing,
    which would be most of the trace and slow the host."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def _within(value: float, limit: str) -> bool:
    op, bound = limit.split()
    bound = float(bound)
    return {"==": value == bound, ">=": value >= bound,
            "<=": value <= bound}[op]


def _kernel_work_lines(record: Record) -> List[str]:
    from chipbench import work

    lines = []
    for kernel in sorted({k for k, *_ in record.launches}):
        calls = [l for l in record.launches if l[0] == kernel]
        counts = work.of(kernel)
        ops = sum(counts.ops(*l[1:]) for l in calls)
        nbytes = sum(counts.hbm_bytes(*l[1:]) for l in calls)
        lines.append(f"kernel {kernel}: {len(calls)} launches, "
                     f"{ops} int32 ops, {nbytes} bytes (logical)")
    return lines
