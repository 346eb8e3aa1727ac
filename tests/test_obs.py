"""The observability layer (DESIGN.md §13): inertness, twin trace
parity, and the export pipeline.

Three contracts are pinned here:

  * **Inertness** — attaching observability (``obs=None`` vs a
    zero-capacity tracer vs a live tracer+telemetry) changes NOTHING
    the differential suites compare: ``PARITY_COUNTERS``, tier logs,
    exact HBM LRU order, host sets, prefetch logs, and per-request
    token streams are bit-identical across all three configurations
    for every backend combination.
  * **Twin trace parity** — the scalar :class:`SlotOracle` and the
    vectorized :class:`SlotMachine` emit bit-identical event streams
    (same kinds, same lanes, same ORDER) for the same arrival trace:
    the trace is a differential axis one level finer than the
    counters, and a pinned golden run locks the schema itself.
  * **Export pipeline** — ``Observability.export_json`` round-trips
    through ``tools/trace_view.py`` into Chrome ``trace_event`` JSON
    (instant + counter + complete events under ``traceEvents``).
"""

import itertools
import json
import sys
import time
import tracemalloc
from contextlib import nullcontext
from pathlib import Path

import numpy as np
import pytest

from strategies import ArrivalSpec, build_poisson_arrivals, drive_slots
from repro.obs import (EV_ADMIT, EV_COMPLETE, EV_EVICT, EV_GCD_EXCHANGE,
                       EV_PREFETCH, EVENT_FIELDS, Observability, attach,
                       profile, trace_diff)
from repro.obs.telemetry import Progress, StreamingHist, Telemetry
from repro.obs.trace import EventTracer, TraceEvent
from repro.serving.kv_cache import PARITY_COUNTERS
from repro.serving.slots import SlotMachine, SlotOracle

TOOLS = Path(__file__).resolve().parents[1] / "tools"
sys.path.insert(0, str(TOOLS))

from trace_view import convert


# --------------------------------------------------------------------------- #
# event ring unit behavior                                                    #
# --------------------------------------------------------------------------- #

def test_ring_records_all_lanes_and_defaults():
    tr = EventTracer(capacity=8)
    tr.emit(EV_ADMIT, tick=3, slot=1, req=7)
    tr.emit(EV_EVICT, page=42, tenant=2)
    assert len(tr) == 2 and tr.total == 2 and tr.dropped == 0
    ev = tr.events()
    assert ev[0] == TraceEvent(EV_ADMIT, 3, 1, 7, -1, -1, -1, -1)
    assert ev[1].page == 42 and ev[1].tenant == 2 and ev[1].tick == -1
    assert ev[0].name == "admit" and ev[1].name == "evict"
    assert tr.as_array().shape == (2, len(EVENT_FIELDS))


def test_ring_wraparound_keeps_newest_and_counts_dropped():
    tr = EventTracer(capacity=4)
    for i in range(11):
        tr.emit(EV_ADMIT, req=i)
    assert tr.total == 11 and len(tr) == 4 and tr.dropped == 7
    assert [e.req for e in tr.events()] == [7, 8, 9, 10]   # oldest first
    tr.clear()
    assert tr.total == 0 and len(tr) == 0


def test_zero_capacity_ring_is_a_pure_counter():
    tr = EventTracer(capacity=0)
    for i in range(5):
        tr.emit(EV_PREFETCH, page=i)
    assert tr.total == 5 and len(tr) == 0 and tr.dropped == 5
    assert tr.events() == [] and tr.as_array().shape == (0, 8)


def test_trace_diff_axes():
    a, b = EventTracer(16), EventTracer(16)
    for t in (a, b):
        t.emit(EV_ADMIT, slot=0, req=1)
    assert trace_diff(a, b) is None
    b.emit(EV_EVICT, page=9)                   # b is longer
    i, ea, eb = trace_diff(a, b)
    assert i == 1 and ea is None and eb.kind == EV_EVICT
    a.emit(EV_EVICT, page=8)                   # same kind, lane differs
    i, ea, eb = trace_diff(a, b)
    assert i == 1 and ea.page == 8 and eb.page == 9
    # equal retained rows but different totals (wrapped history) differ
    c, d = EventTracer(1), EventTracer(1)
    c.emit(EV_ADMIT)
    d.emit(EV_EVICT)
    d.emit(EV_ADMIT)
    assert trace_diff(c, d) == (1, None, None)


# --------------------------------------------------------------------------- #
# telemetry / histograms / progress                                           #
# --------------------------------------------------------------------------- #

def test_streaming_hist_exact_accumulators_and_quantiles():
    h = StreamingHist()
    for v in [0, 1, 1, 2, 3, 7, 8, 100]:
        h.add(v)
    s = h.summary()
    assert s["count"] == 8 and s["sum"] == 122
    assert s["min"] == 0 and s["max"] == 100
    assert h.quantile(0.0) <= h.quantile(0.5) <= h.quantile(1.0)
    assert h.quantile(1.0) >= 100               # upper-bound estimate
    assert s["buckets"]["0"] == 1               # the zero bucket
    h2 = StreamingHist()
    assert h2.quantile(0.5) == 0 and h2.summary()["count"] == 0


def test_telemetry_gauge_rings_are_bounded():
    t = Telemetry(capacity=4)
    for i in range(10):
        t.gauge("x", i, tick=i)
    assert t.gauges["x"] == [[i, float(i)] for i in range(6, 10)]
    t.observe("lat", 5)
    exp = t.export()
    assert exp["hists"]["lat"]["count"] == 1
    assert exp["gauges"]["x"][0] == [6, 6.0]


def test_progress_quiet_suppresses_output(capsys):
    p = Progress(100, label="build", quiet=True, interval_s=0.0)
    for _ in range(100):
        p.advance()
    rep = p.finish()
    assert capsys.readouterr().err == ""
    assert rep["n"] == 100 and rep["per_s"] > 0 and p.rate > 0


def test_progress_prints_throttled_lines(capsys):
    p = Progress(50, label="reg", quiet=False, interval_s=0.0,
                 stream=sys.stderr)
    for _ in range(50):
        p.advance()
    p.finish()
    err = capsys.readouterr().err
    assert "reg" in err and "50/50" in err and "/s" in err


# --------------------------------------------------------------------------- #
# kernel profiling ledger                                                     #
# --------------------------------------------------------------------------- #

def test_kernel_scope_disabled_leaves_no_ledger():
    profile.reset()
    assert not profile.enabled()
    with profile.kernel_scope("noop", items=3):
        pass
    assert profile.summary() == {}


def test_profiling_context_accumulates_and_restores():
    profile.reset()
    with profile.profiling():
        assert profile.enabled()
        for _ in range(2):
            with profile.kernel_scope("k", items=5):
                pass
    assert not profile.enabled()
    rec = profile.summary()["k"]
    assert rec["calls"] == 2 and rec["items"] == 10
    assert rec["wall_s"] >= 0.0
    profile.reset()
    assert profile.summary() == {}


def test_kernel_wrappers_feed_the_ledger():
    from repro.kernels.ops import divisibility_scan, gcd_batch

    profile.reset()
    with profile.profiling():
        divisibility_scan([6, 10, 15], [2, 3, 5])
        gcd_batch([6, 15], [10, 21], [2, 3, 5, 7])
    led = profile.summary()
    assert led["divisibility_scan"]["calls"] == 1
    assert led["divisibility_scan"]["items"] == 3
    assert led["gcd_batch"]["items"] == 2


# --------------------------------------------------------------------------- #
# program spans                                                               #
# --------------------------------------------------------------------------- #

def test_span_off_reads_no_clock_and_allocates_nothing(monkeypatch):
    calls = []
    monkeypatch.setattr(profile, "_clock", lambda: calls.append(1) or 0.0)
    profile.clear_spans()
    assert not profile.recorded()
    # every call returns the one shared null context: no object is built
    assert profile.span("x") is profile.span("y", n=3) is profile._NULL
    tracemalloc.start()
    try:
        start, _ = tracemalloc.get_traced_memory()
        tracemalloc.reset_peak()
        for _ in itertools.repeat(None, 10_000):
            with profile.span("slots.step"):
                pass
        now, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert now <= start + 512 and peak <= start + 512   # the loop's own
    profile.count(n=1)
    assert calls == [] and profile.spans() == []


def test_span_off_serving_keeps_no_records():
    profile.clear_spans()
    eng = _drive(SlotMachine, "vec", False, None)
    assert eng.ticks > 0 and profile.spans() == []


def test_recording_nests_counts_and_restores():
    ticks = iter(range(100))
    with profile.recording(clock=lambda: float(next(ticks))) as kept:
        with profile.span("outer", a=1) as outer:
            with profile.span("inner"):
                profile.count(b=2)
            profile.count(c=3)
        with profile.span("second"):
            pass
    assert not profile.recorded()
    inner, out, second = kept
    assert (out.name, out.parent, out.start, out.end) == ("outer", None,
                                                          0.0, 3.0)
    assert out is outer and out.counts == {"a": 1, "c": 3}
    assert (inner.parent, inner.start, inner.end) == (out.id, 1.0, 2.0)
    assert inner.counts == {"b": 2} and second.parent is None
    assert out.as_dict()["counts"] == {"a": 1, "c": 3}
    # a sink takes the spans instead of the kept list
    got = []
    with profile.recording(sink=got.append) as kept:
        with profile.span("s"):
            pass
    assert kept == [] and [r.name for r in got] == ["s"]


def _kernel_machine():
    m = SlotMachine(kv="vec", max_batch=3, page_size=4, hbm_pages=16,
                    prefill_tokens=12)
    m.pages.discover = "kernel"
    rng = np.random.default_rng(3)
    shared = list(rng.integers(0, 40, size=8))
    for i in range(5):
        m.submit(shared + list(rng.integers(0, 40, size=6 + i)),
                 max_new_tokens=3)
    return m


def test_spans_nest_inside_the_tick_and_the_rebuild():
    m = _kernel_machine()
    before = m.pages.bulk_refreshes
    with profile.recording() as kept:
        m.run_until_idle()
    by_id = {r.id: r for r in kept}
    steps = [r for r in kept if r.name == "slots.step"]
    assert [r.counts["tick"] for r in steps] == list(range(m.ticks))
    assert all(r.parent is None for r in steps)
    for r in kept:
        if r.parent is not None:            # every child inside its parent
            p = by_id[r.parent]
            assert p.start <= r.start <= r.end <= p.end
    refreshes = [r for r in kept if r.name == "pages.refresh"]
    assert len(refreshes) == m.pages.bulk_refreshes - before > 0
    for ref in refreshes:
        assert by_id[ref.parent].name == "pages.touch_batch"
        assert by_id[by_id[ref.parent].parent].name == "slots.step"
        kids = {r.name for r in kept if r.parent == ref.id}
        assert {"tables.pack", "kernels.divisibility_scan",
                "tables.candidates", "kernels.factorize_batch",
                "tables.rows", "pages.install"} <= kids
        assert {"pages", "queries", "composites", "pool",
                "candidates"} <= set(ref.counts)
    for name in ("divisibility_scan", "factorize_batch"):
        for k in (r for r in kept if r.name == f"kernels.{name}"):
            fetch = [r for r in kept if r.parent == k.id]
            assert [r.name for r in fetch] == [f"kernels.{name}.fetch"]
            assert k.counts["padded_n"] >= k.counts["n"] > 0
    admits = [r for r in kept if r.name == "slots.admit"]
    assert sum(r.counts.get("admitted", 0) for r in steps) == 5
    assert len(admits) == len(steps)
    assert {by_id[r.parent].name for r in kept
            if r.name == "slots.complete"} == {"slots.step"}


@pytest.mark.parametrize("kv", ["sharded", "elastic"])
def test_every_backend_emits_the_rebuild_span(kv):
    m = SlotMachine(kv=kv, **CFG)
    before = m.pages.bulk_refreshes
    with profile.recording() as kept:
        drive_slots(m, build_poisson_arrivals(GOLDEN_SPEC))
    refreshes = [r for r in kept if r.name == "pages.refresh"]
    assert len(refreshes) == m.pages.bulk_refreshes - before > 0
    ids = {r.id for r in refreshes}
    assert {r.name for r in kept if r.parent in ids} >= {
        "tables.pack", "kernels.sharded_exchange", "tables.candidates",
        "tables.rows", "pages.install"}
    exchanges = [r for r in kept if r.name == "kernels.sharded_exchange"]
    assert len(exchanges) == len(refreshes)
    for ex in exchanges:
        assert [r.name for r in kept if r.parent == ex.id] == [
            "kernels.sharded_exchange.fetch"]
        c = ex.counts
        # logical sizes: the imbalance and the cross share of a rebuild
        assert 0 <= c["local_min"] <= c["local_max"] <= c["c"]
        assert 0 <= c["cross"] <= c["gathered"]
        assert c["gathered"] % c["shards"] == 0
        assert 0 < c["q_max"] <= c["q"]


def test_kernel_ledger_unchanged_with_spans_on():
    from repro.kernels.ops import divisibility_scan, factorize_batch, \
        gcd_batch

    def launches():
        divisibility_scan([6, 10, 15], [2, 3, 5])
        factorize_batch([6, 35], [2, 3, 5, 7])
        gcd_batch([6, 15], [10, 21], [2, 3, 5, 7])

    with profile.profiling():
        launches()
        off = profile.summary()
    with profile.profiling(), profile.recording() as kept:
        launches()
        on = profile.summary()
    strip = lambda led: {k: (v["calls"], v["items"]) for k, v in led.items()}
    assert strip(on) == strip(off)
    for name, rec in on.items():
        span = next(r for r in kept if r.name == f"kernels.{name}")
        # the ledger's wall is the span's own pair of clock reads
        assert rec["wall_s"] == span.end - span.start
        assert span.counts["items"] == rec["items"]


def test_factorize_hits_counts_the_factors_returned():
    from repro.kernels.ops import factorize_batch

    with profile.recording() as kept:
        facs, _ = factorize_batch([6, 35, 143, 101, 2 * 3 * 5 * 7, 1],
                                  [2, 3, 5, 7, 11, 13])
    span = next(r for r in kept if r.name == "kernels.factorize_batch")
    assert span.counts["hits"] == sum(map(len, facs)) == 10


@pytest.mark.parametrize("over_the_cap", [False, True])
def test_compacting_wrappers_count_hits_and_dense(over_the_cap):
    """``hits`` on the scan is the dense mask's set count; ``dense`` is 1
    on both wrappers only when a launch's hits overflow their slots and
    the dense mask is copied instead."""
    import math

    from repro.kernels.ops import as_digits, divisibility_scan, \
        factorize_batch
    from repro.kernels.ref import divisibility_mask_ref

    primes = [2, 3, 5, 7, 11, 13, 17, 19, 23, 29, 31, 37, 41, 43, 47]
    # 15 hits a composite overflow the 4 slots a padded row keeps
    vals = ([math.prod(primes)] * 256 if over_the_cap
            else [6, 35, 143, 101, 2 * 3 * 5 * 7, 1])
    with profile.recording() as kept:
        divisibility_scan(vals, primes)
        factorize_batch(vals, primes)
    scan, fact = (next(r for r in kept if r.name == f"kernels.{name}")
                  for name in ("divisibility_scan", "factorize_batch"))
    assert scan.counts["hits"] == \
        divisibility_mask_ref(as_digits(vals), primes).sum()
    assert scan.counts["dense"] == fact.counts["dense"] == int(over_the_cap)


def test_sharded_exchange_counts_hits_and_dense():
    """``hits`` on the sharded exchange is the set count of every
    shard's dense mask together, a cross composite counted in each shard
    that owns one of its primes; ``dense`` is 0 while no shard's hits
    overflow their slots."""
    from repro.kernels.ref import divisibility_mask_ref
    from repro.serving.kv_cache_sharded import ShardedPagedKVCache

    kv = ShardedPagedKVCache(hbm_pages=16, page_size=4, n_shards=4,
                             mesh=None, stripes_per_shard=1000)
    rng = np.random.default_rng(5)
    for r in range(6):
        kv.register_request(r, list(rng.integers(0, 200, size=24)))
    with profile.recording() as kept:
        kv.refresh_tables()
    assert kv.last_scan.cross_composites > 0
    primes = [p for d in range(kv._next_page)
              if (p := kv.assigner.prime_of(d)) is not None]
    ex = next(r for r in kept if r.name == "kernels.sharded_exchange")
    assert ex.counts["hits"] == \
        divisibility_mask_ref(kv.registry.limbs_array(), primes).sum() > 0
    assert ex.counts["dense"] == 0


class _StubModel:
    """A model with the decode interface and no weights: logits favour
    token (position + 1)."""

    vocab = 16

    def init_cache(self, batch, max_seq):
        import jax.numpy as jnp
        return {"len": jnp.zeros((batch,), jnp.int32)}

    def decode_step(self, params, batch, cache):
        import jax
        import jax.numpy as jnp
        nxt = (cache["len"] + 1) % self.vocab
        logits = jax.nn.one_hot(nxt, self.vocab)[:, None, :]
        return logits, dict(cache, len=cache["len"] + 1)


def test_engine_spans_and_stamps_share_the_clock():
    from repro.serving.engine import ServingEngine

    eng = ServingEngine(_StubModel(), params=None, max_batch=2,
                        max_seq=32, page_size=4, hbm_pages=32)
    t0 = time.perf_counter()
    with profile.recording() as kept:
        for i in range(3):
            eng.submit([1, 2, 3 + i], max_new_tokens=3)
        done = eng.run_until_idle()
    t1 = time.perf_counter()
    assert len(done) == 3
    for r in done:
        assert t0 <= r.submit_t <= r.first_token_t <= r.done_t <= t1
    steps = [r for r in kept if r.name == "engine.step"]
    assert [r.counts["tick"] for r in steps] == list(range(eng.steps))
    ids = {r.id for r in steps}
    kids = [r.name for r in kept if r.parent in ids]
    for name in ("engine.admit", "engine.decode", "engine.fetch",
                 "pages.touch_batch"):
        assert kids.count(name) == len(steps), name
    # the engine's first-token stamp falls inside the step that made it
    first = min(r.first_token_t for r in done)
    assert any(s.start <= first <= s.end for s in steps)


# --------------------------------------------------------------------------- #
# inertness: attaching obs never perturbs placement                           #
# --------------------------------------------------------------------------- #

SPEC = ArrivalSpec(seed=11, n_requests=18, rate=1.6, burst_frac=0.2,
                   max_prompt=22, max_new=8, shared_pool=12)
CFG = dict(max_batch=4, page_size=4, hbm_pages=24, prefetch_budget=2,
           reread_window=2, prefill_tokens=12, preempt_wait=3)

BACKENDS = [
    ("vec", False), ("scalar", False), ("sharded", False),
    ("elastic", False), ("vec", True), ("scalar", True),
]


def _drive(cls, kv, dedup, obs):
    # dedup rides the tenant namespace (engine factory contract)
    eng = cls(kv=kv, dedup=dedup, tenants=2 if dedup else None, obs=obs,
              **CFG)
    drive_slots(eng, build_poisson_arrivals(SPEC))
    return eng


def _placement_state(eng):
    return (
        tuple(getattr(eng.pages.stats, f) for f in PARITY_COUNTERS),
        tuple(eng.tier_log),
        tuple(eng.pages.hbm.items()),
        frozenset(eng.pages.host),
        tuple(eng.pages.prefetch_log),
        tuple(tuple(r.generated) for r in eng.requests),
        tuple((r.first_tick, r.done_tick, r.preemptions)
              for r in eng.requests),
    )


# the recorder-on cases are the same sweep with program spans recorded
SWEEP = ([pytest.param(kv, dedup, False, id=f"{kv}-{dedup}")
          for kv, dedup in BACKENDS]
         + [pytest.param(kv, dedup, True, id=f"{kv}-{dedup}-spans")
            for kv, dedup in BACKENDS])


@pytest.mark.parametrize("cls", [SlotMachine, SlotOracle])
@pytest.mark.parametrize("kv,dedup,spans", SWEEP)
def test_tracing_off_parity_sweep(cls, kv, dedup, spans):
    """obs=None, a zero-capacity tracer, and a live tracer+telemetry
    all produce byte-identical placement — the inertness contract —
    with the span recorder off and on."""
    base = _placement_state(_drive(cls, kv, dedup, None))
    zero = Observability(trace_capacity=0, telemetry=False)
    live = Observability(trace_capacity=4096)
    with (profile.recording() if spans else nullcontext()) as kept:
        if spans:
            assert _placement_state(_drive(cls, kv, dedup, None)) == base
        assert _placement_state(_drive(cls, kv, dedup, zero)) == base
        eng = _drive(cls, kv, dedup, live)
    assert _placement_state(eng) == base
    # the live run actually observed something
    assert live.trace.total > 0
    assert live.telemetry.ticks_seen == eng.ticks
    # and the zero-capacity tracer counted the same emissions
    assert zero.trace.total == live.trace.total
    if spans and cls is SlotMachine:
        # three drives, one slots.step span per tick of each
        assert sum(r.name == "slots.step" for r in kept) == 3 * eng.ticks
    assert not profile.recorded()


# --------------------------------------------------------------------------- #
# twin trace parity + the pinned golden run                                   #
# --------------------------------------------------------------------------- #

GOLDEN_SPEC = ArrivalSpec(seed=5, n_requests=10, rate=1.2, max_prompt=16,
                          max_new=6, shared_pool=8)


def _traced(cls, kv="vec"):
    obs = Observability(trace_capacity=8192)
    eng = cls(kv=kv, obs=obs, **CFG)
    drive_slots(eng, build_poisson_arrivals(GOLDEN_SPEC))
    return eng, obs


@pytest.mark.parametrize("kv", ["vec", "scalar"])
def test_twin_event_streams_bit_identical(kv):
    _, mo = _traced(SlotMachine, kv)
    _, oo = _traced(SlotOracle, kv)
    assert trace_diff(mo.trace, oo.trace) is None


def test_golden_trace_structure():
    """Structural pins on the golden run: every request admitted once
    and completed once, in tick order, with prefill chunks covering
    each prompt before its completion."""
    eng, obs = _traced(SlotMachine)
    evs = obs.trace.events()
    admits = [e for e in evs if e.name == "admit"]
    completes = [e for e in evs if e.name == "complete"]
    # a preempted request is re-admitted on resume: admits per request
    # = 1 + its preemption count; completes are exactly one each
    assert {e.req for e in admits} == set(range(10))
    for r in eng.requests:
        assert sum(1 for e in admits if e.req == r.req_id) \
            == 1 + r.preemptions
    assert sorted(e.req for e in completes) == list(range(10))
    ticks = [e.tick for e in evs if e.tick >= 0]
    assert ticks == sorted(ticks)               # stream is tick-ordered
    assert all(e.slot >= 0 for e in admits + completes)
    # admit precedes complete per request
    first_admit = {e.req: i for i, e in reversed(list(enumerate(evs)))
                   if e.name == "admit"}
    for i, e in enumerate(evs):
        if e.name == "complete":
            assert first_admit[e.req] < i
    # prefetch/evict events carry page attribution only
    for e in evs:
        if e.name in ("prefetch", "evict"):
            assert e.page >= 0 and e.slot == -1


def test_golden_trace_pinned_prefix():
    """The exact head of the golden machine trace — pins the event
    schema and emission order (regenerate deliberately if the serving
    semantics change)."""
    _, obs = _traced(SlotMachine)
    head = [(e.name, e.tick, e.slot, e.req) for e in obs.trace.events()[:6]]
    assert head == GOLDEN_HEAD, head


# filled from the deterministic golden run; see test above
GOLDEN_HEAD = [
    ("admit", 1, 0, 0),
    ("prefill_chunk", 1, 0, 0),
    ("prefetch", -1, -1, -1),
    ("prefetch", -1, -1, -1),
    ("prefetch", -1, -1, -1),
    ("admit", 2, 1, 1),
]


# --------------------------------------------------------------------------- #
# cache-level and sharded-event emission                                      #
# --------------------------------------------------------------------------- #

def test_sharded_refresh_emits_gcd_exchange_events():
    from repro.serving.kv_cache_sharded import ShardedPagedKVCache

    cache = ShardedPagedKVCache(hbm_pages=16, page_size=4, n_shards=2,
                                mesh=None)
    obs = attach(cache, Observability())
    cache.register_request(0, list(range(20)))
    cache.refresh_tables()
    exch = [e for e in obs.trace.events() if e.kind == EV_GCD_EXCHANGE]
    assert len(exch) == cache.n_shards
    assert sorted(e.shard for e in exch) == list(range(cache.n_shards))
    assert sum(e.arg for e in exch) == sum(cache.last_scan.local_composites)


def test_attach_detach():
    m = SlotMachine(kv="vec", **CFG)
    obs = attach(m, Observability())
    assert m.obs is obs and m.pages.obs is obs
    attach(m, None)
    assert m.obs is None and m.pages.obs is None


# --------------------------------------------------------------------------- #
# export pipeline -> Chrome trace_event                                       #
# --------------------------------------------------------------------------- #

def test_export_roundtrip_through_trace_view(tmp_path):
    with profile.recording():
        eng, obs = _traced(SlotMachine)
    kept = profile.spans()
    profile.reset()
    with profile.profiling():
        with profile.kernel_scope("fake_kernel", items=7):
            pass
    path = tmp_path / "obs.json"
    obs.export_json(path)
    profile.clear_spans()
    payload = json.loads(path.read_text())
    assert payload["schema"]["1"] == "admit"
    assert payload["trace"]["total"] == obs.trace.total
    assert payload["telemetry"]["ticks_seen"] == eng.ticks
    assert payload["kernel_launches"]["fake_kernel"]["items"] == 7
    assert [sp["name"] for sp in payload["spans"]] == [r.name for r in kept]

    chrome = convert(payload)
    evs = chrome["traceEvents"]
    assert chrome["displayTimeUnit"] == "ms"
    phases = {e["ph"] for e in evs}
    assert {"i", "C", "X", "M"} <= phases
    inst = [e for e in evs if e["ph"] == "i"]
    assert len(inst) == len(obs.trace.events())
    assert all("name" in e for e in evs)
    assert all("ts" in e for e in evs if e["ph"] != "M")
    # counter events carry their gauge value under args[name]
    ctr = next(e for e in evs if e["ph"] == "C")
    assert ctr["args"][ctr["name"]] is not None
    # spans are drawn at their recorded times, microseconds from the first
    t0 = min(r.start for r in kept)
    spans = [e for e in evs if e["ph"] == "X"]
    assert len(spans) == len(kept)
    for e, r in zip(spans, kept):
        assert e["pid"] == 1 and e["name"] == r.name
        assert e["ts"] == pytest.approx((r.start - t0) * 1e6)
        assert e["dur"] == pytest.approx((r.end - r.start) * 1e6)
    # ring events sit at the start of their tick's slots.step span
    step_at = {e["args"]["tick"]: e["ts"] for e in spans
               if e["name"] == "slots.step"}
    for e in inst:
        assert e["ts"] == step_at[e["args"]["tick"]]
    assert ctr["ts"] in step_at.values()
    # the whole thing serializes (what chrome://tracing loads)
    json.dumps(chrome)


def test_trace_view_without_spans_lays_ticks_1ms_apart():
    _, obs = _traced(SlotOracle)
    chrome = convert(obs.export())
    inst = [e for e in chrome["traceEvents"] if e["ph"] == "i"]
    assert inst and all(e["ts"] == e["args"]["tick"] * 1000 for e in inst)
    assert not [e for e in chrome["traceEvents"] if e["ph"] == "X"]


def test_trace_view_cli(tmp_path, capsys):
    from trace_view import main as tv_main

    _, obs = _traced(SlotOracle)
    src = tmp_path / "obs.json"
    dst = tmp_path / "chrome.json"
    obs.export_json(src)
    out = tv_main([str(src), str(dst)])
    assert dst.exists() and out["traceEvents"]
    assert "wrote" in capsys.readouterr().out
    assert json.loads(dst.read_text())["displayTimeUnit"] == "ms"
