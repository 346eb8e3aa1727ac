"""Shared test-workload generators: hypothesis strategies + the
deterministic builders behind them.

Every randomized workload the suite drives caches with lives here, in
two layers:

  1. **Deterministic builders** — pure functions of a small spec
     (seed + sizes) that expand into concrete workloads:
     :func:`build_kv_ops` / :func:`apply_kv_ops` for paged-KV request
     streams, :func:`drive_kv` (the classic serving parity driver),
     :func:`build_expert_sets` / :func:`drive_expert` for router-driven
     MoE expert workloads, :func:`trace_zoo` / :func:`adversarial_trace`
     for simulator traces.  The ad-hoc randomized loops that used to
     live inline in ``tests/test_serving.py`` / ``tests/test_engine.py``
     now call these.
  2. **Hypothesis strategies** (via ``hypothesis_compat`` — clean SKIP
     when the package is absent) that sample the *specs*:
     :func:`kv_workload_specs` for serving-cache differential fuzzing
     (chain topologies with shared prefixes, 1-slot HBM, registry
     drops, eviction-adversarial sweeps),
     :func:`expert_workload_specs` for expert-cache fuzzing (skewed
     router popularity, repeated-group / disjoint-partition schedules,
     ``max_group`` overflow), :func:`trace_specs` for engine traces,
     :func:`adversarial_stream_specs` for recency-thrashing access
     streams.

Sampling specs rather than raw streams keeps shrinking effective (a
failing case minimizes to a tiny seed + sizes tuple) and lets the
differential tests replay the IDENTICAL abstract op sequence against
every cache implementation — selectors resolve against live state, so
two bit-equal caches see bit-equal concrete streams.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence, Tuple

import numpy as np

from hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

__all__ = [
    "KVWorkloadSpec", "build_kv_ops", "apply_kv_ops", "drive_kv",
    "kv_workload_specs", "trace_zoo", "trace_specs", "make_trace",
    "adversarial_trace", "adversarial_stream_specs",
    "LimbUniverseSpec", "build_limb_universe", "limb_universe_specs",
    "ElasticEventSpec", "build_failure_schedule", "apply_elastic_event",
    "elastic_event_specs",
    "ExpertWorkloadSpec", "build_expert_sets", "drive_expert",
    "expert_workload_specs",
    "TenantMixSpec", "build_tenant_requests", "drive_tenants",
    "tenant_mix_specs", "dedup_mix_specs",
    "ArrivalSpec", "build_poisson_arrivals", "drive_slots",
    "arrival_specs",
    "HAVE_HYPOTHESIS", "given", "settings", "st",
]


# --------------------------------------------------------------------------- #
# paged-KV workloads (serving tier)                                           #
# --------------------------------------------------------------------------- #

@dataclass(frozen=True)
class KVWorkloadSpec:
    """Compact description of a serving workload; expanded by
    :func:`build_kv_ops` into an abstract op sequence."""

    seed: int = 0
    n_requests: int = 12
    n_touches: int = 160
    key_space: int = 400
    shared_pool: int = 32          # tokens available for shared prefixes
    max_tail: int = 28             # per-request tail length bound
    release: bool = True           # retire old requests mid-stream
    drop_primes: bool = False      # out-of-band Algorithm-1 prime drops
    sweeps: int = 0                # eviction-adversarial full-chain sweeps


def build_kv_ops(spec: KVWorkloadSpec) -> List[Tuple]:
    """Expand a spec into an abstract op list.

    Ops use *selectors* (resolved modulo live state at apply time), so
    the same list drives any cache implementation:

      ("register", rid, tokens)  — submit a request's prompt
      ("touch", a, b)            — touch live request a-th, page b-th
      ("sweep", a)               — touch every page of a live request in
                                   chain order (sequential re-read — the
                                   LRU-adversarial scan pattern)
      ("release", )              — retire the oldest live request
      ("drop", d)                — assigner.release a page's L2 prime
                                   (registry drop -> table rebuild path)
    """
    rng = np.random.default_rng(spec.seed)
    shared = list(rng.integers(0, spec.key_space, size=spec.shared_pool))
    ops: List[Tuple] = []
    per_req = max(1, spec.n_touches // max(1, spec.n_requests))
    for r in range(spec.n_requests):
        pfx = int(rng.integers(0, spec.shared_pool))
        tail = list(rng.integers(0, spec.key_space,
                                 size=int(rng.integers(4, spec.max_tail))))
        ops.append(("register", r, tuple(shared[:pfx] + tail)))
        if spec.drop_primes and rng.integers(4) == 0:
            ops.append(("drop", int(rng.integers(1 << 30))))
        for _ in range(per_req):
            ops.append(("touch", int(rng.integers(1 << 30)),
                        int(rng.integers(1 << 30))))
        if spec.sweeps and rng.integers(max(1, spec.n_requests
                                            // spec.sweeps)) == 0:
            ops.append(("sweep", int(rng.integers(1 << 30))))
        if spec.release and r > 6 and rng.integers(3) == 0:
            ops.append(("release",))
    return ops


def apply_kv_ops(kv, ops: Sequence[Tuple], schedule=None,
                 on_event=None) -> List[str]:
    """Replay an abstract op list against one cache; returns the tier
    string of every touch (the differential-comparison payload).

    ``schedule`` (a :func:`build_failure_schedule` dict: op index ->
    event list) injects chaos events BEFORE the op at that index.  Each
    event goes through ``on_event(kv, event)`` when given, else
    :func:`apply_elastic_event` — which no-ops kill/resize on caches
    without elastic hooks, so the SAME schedule replays against the
    scalar oracle and the elastic cache (the parity contract's whole
    point: elastic events must be invisible to placement).
    """
    from repro.core.primes import CacheLevel

    tiers: List[str] = []
    live: List[int] = []
    fire = on_event if on_event is not None else apply_elastic_event
    for i, op in enumerate(ops):
        if schedule:
            for ev in schedule.get(i, ()):
                fire(kv, ev)
        kind = op[0]
        if kind == "register":
            _, rid, tokens = op
            kv.register_request(rid, list(tokens))
            live.append(rid)
        elif kind == "touch":
            _, a, b = op
            if not live:
                continue
            rid = live[a % len(live)]
            chain = kv.chains.get(rid) or ()
            if chain:
                tiers.append(kv.touch(rid, b % len(chain)))
        elif kind == "sweep":
            (_, a) = op
            if not live:
                continue
            rid = live[a % len(live)]
            chain = kv.chains.get(rid) or ()
            if chain:
                tiers.extend(kv.touch_batch([(rid, j)
                                             for j in range(len(chain))]))
        elif kind == "release":
            if live:
                kv.release_request(live.pop(0))
        elif kind == "drop":
            (_, d) = op
            if kv._next_page:
                kv.assigner.release(d % kv._next_page, CacheLevel.L2)
        else:                       # pragma: no cover - builder invariant
            raise ValueError(f"unknown op {kind!r}")
    return tiers


def drive_kv(kv, seed: int, n_requests: int = 16,
             n_touches: int = 400) -> List[str]:
    """The classic serving parity driver (shared-prefix request mix,
    interleaved registration and touches, releases) — byte-identical to
    the loop that used to live in ``tests/test_serving.py``."""
    rng = np.random.default_rng(seed)
    shared = list(rng.integers(0, 400, size=32))
    tiers: List[str] = []
    live: List[int] = []
    for r in range(n_requests):
        pfx = int(rng.integers(0, 32))
        tail = list(rng.integers(0, 400, size=int(rng.integers(4, 28))))
        kv.register_request(r, shared[:pfx] + tail)
        live.append(r)
        for _ in range(n_touches // n_requests):
            q = live[int(rng.integers(len(live)))]
            if kv.chains[q]:
                tiers.append(kv.touch(q, int(rng.integers(
                    len(kv.chains[q])))))
        if len(live) > 6 and rng.integers(3) == 0:
            kv.release_request(live.pop(0))
    return tiers


def kv_workload_specs():
    """Strategy over serving workload specs, biased toward the edges the
    parity suite cares about: degenerate 1-slot HBM interleavings come
    from the caller's cache config; this spec covers chain topology
    (shared-prefix depth), registry drops, and adversarial sweeps."""
    return st.builds(
        KVWorkloadSpec,
        seed=st.integers(min_value=0, max_value=2**16),
        n_requests=st.integers(min_value=3, max_value=18),
        n_touches=st.integers(min_value=10, max_value=240),
        key_space=st.sampled_from([60, 400]),
        shared_pool=st.sampled_from([8, 32]),
        max_tail=st.sampled_from([6, 28]),
        release=st.booleans(),
        drop_primes=st.booleans(),
        sweeps=st.sampled_from([0, 2]),
    )


# --------------------------------------------------------------------------- #
# chaos fault-injection schedules (elastic tier)                              #
# --------------------------------------------------------------------------- #

@dataclass(frozen=True)
class ElasticEventSpec:
    """Compact description of a chaos fault-injection schedule; expanded
    by :func:`build_failure_schedule` into op-indexed events for
    :func:`apply_kv_ops` / :func:`drive_tenants` (the elastic chaos
    fuzz's input — tests/test_elastic.py)."""

    seed: int = 0
    n_events: int = 4
    kill: bool = True              # shard loss (fail_shard)
    defer: bool = True             # some kills recover lazily (next touch)
    resize: bool = True            # live shard-count changes
    straggle: bool = False         # slow-node reports (controller-driven)
    drop: bool = False             # out-of-band Algorithm-1 prime drops
    shard_choices: Tuple[int, ...] = (2, 4)


def build_failure_schedule(spec: ElasticEventSpec, n_ops: int):
    """Expand a spec into ``{op_index: [event, ...]}`` (events fire
    before the op at that index).  Event tuples:

      ("kill", sel, deferred)  — fail shard sel % n_shards; recover
                                 immediately unless ``deferred`` (then
                                 failover-on-demand recovers it at the
                                 next touch)
      ("resize", n)            — live re-stripe to n shards
      ("straggle", sel, slow)  — node sel reports ``slow``x step times
                                 (meaningful only via a controller's
                                 StragglerMonitor; placement no-op)
      ("drop", sel)            — assigner.release a page's prime — a
                                 WORKLOAD mutation, applied identically
                                 to every cache incl. the oracle
    """
    rng = np.random.default_rng(spec.seed)
    kinds = ([("kill",)] if spec.kill else []) \
        + ([("resize",)] if spec.resize else []) \
        + ([("straggle",)] if spec.straggle else []) \
        + ([("drop",)] if spec.drop else [])
    schedule: dict = {}
    if not kinds or n_ops < 2:
        return schedule
    for _ in range(spec.n_events):
        idx = int(rng.integers(1, n_ops))
        (kind,) = kinds[int(rng.integers(len(kinds)))]
        if kind == "kill":
            ev = ("kill", int(rng.integers(1 << 30)),
                  bool(spec.defer and rng.integers(2)))
        elif kind == "resize":
            ev = ("resize", int(spec.shard_choices[
                int(rng.integers(len(spec.shard_choices)))]))
        elif kind == "straggle":
            ev = ("straggle", int(rng.integers(1 << 30)),
                  float(2.0 + rng.integers(4)))
        else:
            ev = ("drop", int(rng.integers(1 << 30)))
        schedule.setdefault(idx, []).append(ev)
    return schedule


def apply_elastic_event(kv, ev: Tuple) -> None:
    """Default chaos-event dispatcher.  Elastic-only events (kill,
    resize) no-op on caches without the hooks — the oracle replays the
    same schedule and must end bit-identical; ``drop`` mutates the
    workload itself, so it applies to EVERY cache."""
    from repro.core.primes import CacheLevel

    kind = ev[0]
    if kind == "kill":
        if hasattr(kv, "fail_shard"):
            s = ev[1] % kv.n_shards
            kv.fail_shard(s)
            if not ev[2]:
                kv.recover_shard(s)
    elif kind == "resize":
        if hasattr(kv, "resize") and ev[1] != getattr(kv, "n_shards", None):
            kv.resize(ev[1])
    elif kind == "straggle":
        pass                        # needs a controller; placement no-op
    elif kind == "drop":
        if kv._next_page:
            kv.assigner.release(ev[1] % kv._next_page, CacheLevel.L2)
    else:                           # pragma: no cover - builder invariant
        raise ValueError(f"unknown event {kind!r}")


def elastic_event_specs():
    """Strategy over chaos schedules: kill/resize mixes with deferred
    recoveries, optional straggler reports and prime drops."""
    return st.builds(
        ElasticEventSpec,
        seed=st.integers(min_value=0, max_value=2**16),
        n_events=st.integers(min_value=1, max_value=6),
        kill=st.booleans(),
        defer=st.booleans(),
        resize=st.booleans(),
        straggle=st.just(False),
        drop=st.booleans(),
        shard_choices=st.just((2, 4)),
    )


# --------------------------------------------------------------------------- #
# multi-tenant workloads (tenancy tier)                                       #
# --------------------------------------------------------------------------- #

@dataclass(frozen=True)
class TenantMixSpec:
    """Compact description of a mixed-tenant serving workload; expanded
    by :func:`build_tenant_requests` into a tenant-tagged abstract op
    sequence (the tenancy differential fuzz's input —
    tests/test_tenancy.py)."""

    seed: int = 0
    n_tenants: int = 2
    n_requests: int = 10
    n_touches: int = 120
    key_space: int = 300
    shared_pool: int = 24          # per-tenant shared-prefix token pool
    max_tail: int = 20             # per-request tail length bound
    hot_tenant: bool = False       # tenant 0 draws extra zipf-hot touches
    scanner_tenant: bool = False   # last tenant sweeps whole chains
    cross_prefix: bool = False     # tenants submit IDENTICAL token
    #                                prefixes (isolation must still keep
    #                                their pages distinct)
    release: bool = True           # retire old requests mid-stream
    drop_primes: bool = False      # out-of-band Algorithm-1 prime drops


def build_tenant_requests(spec: TenantMixSpec) -> List[Tuple]:
    """Expand a spec into a tenant-tagged abstract op list.

    Ops mirror :func:`build_kv_ops` (selectors resolved modulo live
    state at apply time) with tenant-aware registration:

      ("register", rid, tenant, tokens) — submit a request for a tenant
      ("touch", a, b)                   — touch live request a-th, page b-th
      ("sweep", a)                      — full-chain sequential re-read
                                          (the scanner/adversarial pattern)
      ("release", )                     — retire the oldest live request
      ("drop", d)                       — assigner.release a page's prime
    """
    rng = np.random.default_rng(spec.seed)
    T = max(1, spec.n_tenants)
    pools = [list(rng.integers(0, spec.key_space, size=spec.shared_pool))
             for _ in range(T)]
    if spec.cross_prefix:
        pools = [list(pools[0]) for _ in range(T)]   # identical tokens
    ops: List[Tuple] = []
    per_req = max(1, spec.n_touches // max(1, spec.n_requests))
    scanner = T - 1
    for r in range(spec.n_requests):
        t = int(rng.integers(T))
        pfx = int(rng.integers(0, spec.shared_pool))
        tail_n = int(rng.integers(4, spec.max_tail))
        if spec.scanner_tenant and t == scanner:
            tail_n = spec.max_tail + 8               # long chains to sweep
        tail = list(rng.integers(0, spec.key_space, size=tail_n))
        ops.append(("register", r, t, tuple(pools[t][:pfx] + tail)))
        if spec.drop_primes and rng.integers(4) == 0:
            ops.append(("drop", int(rng.integers(1 << 30))))
        n_t = per_req * (3 if spec.hot_tenant and t == 0 else 1)
        for _ in range(n_t):
            ops.append(("touch", int(rng.integers(1 << 30)),
                        int(rng.integers(1 << 30))))
        if spec.scanner_tenant and t == scanner:
            ops.append(("sweep", r))
        if spec.release and r > 4 and rng.integers(3) == 0:
            ops.append(("release",))
    return ops


def drive_tenants(kv, ops: Sequence[Tuple], step_hook=None,
                  schedule=None, on_event=None) -> List[str]:
    """Replay a tenant-tagged op list against one tenanted cache;
    returns every touch's tier string (the differential-comparison
    payload).  ``step_hook(kv)``, when given, runs after EVERY op — the
    tenancy fuzz passes the namespace isolation checker here so the
    invariant is proven at every intermediate state, not just at the
    end.  ``schedule``/``on_event`` inject chaos events exactly as in
    :func:`apply_kv_ops` (the elastic x tenancy composition fuzz)."""
    from repro.core.primes import CacheLevel

    tiers: List[str] = []
    live: List[int] = []
    fire = on_event if on_event is not None else apply_elastic_event
    for i, op in enumerate(ops):
        if schedule:
            for ev in schedule.get(i, ()):
                fire(kv, ev)
        kind = op[0]
        if kind == "register":
            _, rid, tenant, tokens = op
            kv.register_request(rid, list(tokens), tenant=tenant)
            live.append(rid)
        elif kind == "touch":
            _, a, b = op
            if live:
                rid = live[a % len(live)]
                chain = kv.chains.get(rid) or ()
                if chain:
                    tiers.append(kv.touch(rid, b % len(chain)))
        elif kind == "sweep":
            (_, a) = op
            if live:
                rid = live[a % len(live)]
                chain = kv.chains.get(rid) or ()
                if chain:
                    tiers.extend(kv.touch_batch(
                        [(rid, j) for j in range(len(chain))]))
        elif kind == "release":
            if live:
                kv.release_request(live.pop(0))
        elif kind == "drop":
            (_, d) = op
            if kv._next_page:
                kv.assigner.release(d % kv._next_page, CacheLevel.L2)
        else:                       # pragma: no cover - builder invariant
            raise ValueError(f"unknown op {kind!r}")
        if step_hook is not None:
            step_hook(kv)
    return tiers


def tenant_mix_specs():
    """Strategy over mixed-tenant workload specs, biased toward the
    edges the tenancy parity suite cares about: hot/scanner tenant
    mixes, identical cross-tenant prefixes (content-isolation path),
    releases, and out-of-band prime drops (degenerate quotas come from
    the caller's cache config)."""
    return st.builds(
        TenantMixSpec,
        seed=st.integers(min_value=0, max_value=2**16),
        n_tenants=st.sampled_from([1, 2, 4]),
        n_requests=st.integers(min_value=3, max_value=12),
        n_touches=st.integers(min_value=10, max_value=140),
        key_space=st.sampled_from([60, 300]),
        shared_pool=st.sampled_from([8, 24]),
        max_tail=st.sampled_from([6, 20]),
        hot_tenant=st.booleans(),
        scanner_tenant=st.booleans(),
        cross_prefix=st.booleans(),
        release=st.booleans(),
        drop_primes=st.booleans(),
    )


def dedup_mix_specs():
    """Tenant mixes biased to the dedup paths (tests/test_dedup.py):
    identical cross-tenant token pools are ALWAYS on — the shared-
    system-prompt workload where admissions hit, promote, and COW off
    shared pages — with >= 2 tenants so promotion is reachable.  Prime
    drops stay off (a dropped prime under a refcounted shared page is
    the recycling fuzz's job, not the lifecycle fuzz's)."""
    return st.builds(
        TenantMixSpec,
        seed=st.integers(min_value=0, max_value=2**16),
        n_tenants=st.sampled_from([2, 4]),
        n_requests=st.integers(min_value=4, max_value=12),
        n_touches=st.integers(min_value=10, max_value=140),
        key_space=st.sampled_from([60, 300]),
        shared_pool=st.sampled_from([8, 24]),
        max_tail=st.sampled_from([6, 20]),
        hot_tenant=st.booleans(),
        scanner_tenant=st.booleans(),
        cross_prefix=st.just(True),
        release=st.booleans(),
        drop_primes=st.just(False),
    )


# --------------------------------------------------------------------------- #
# open-loop arrival traces (continuous-batching tier)                         #
# --------------------------------------------------------------------------- #

@dataclass(frozen=True)
class ArrivalSpec:
    """Compact description of an open-loop Poisson arrival trace;
    expanded by :func:`build_poisson_arrivals` into concrete
    (arrival-tick, prompt, max_new, tenant) tuples — the
    continuous-batching differential fuzz's input
    (tests/test_serving_batching.py)."""

    seed: int = 0
    n_requests: int = 24
    rate: float = 1.5              # mean requests per engine tick
    burst_frac: float = 0.0        # fraction front-loaded at tick 0 ...
    silence_ticks: int = 0         # ... followed by this much dead air
    min_prompt: int = 1            # prompt-length bounds: (1, 6) is the
    max_prompt: int = 24           # all-short mix, (40, 90) all-long
    max_new: int = 10              # decode-demand upper bound (ragged)
    shared_pool: int = 16          # tokens available for shared prefixes
    key_space: int = 200
    n_tenants: int = 1


def build_poisson_arrivals(spec: ArrivalSpec) -> List[Tuple]:
    """Expand a spec into ``(arrival, prompt, max_new, tenant)`` tuples
    in submission order.  Inter-arrival gaps are exponential at
    ``spec.rate`` (open-loop: the trace does not react to the engine);
    ``burst_frac``/``silence_ticks`` shape the burst-then-silence
    adversarial mix.  Prompts draw a shared prefix + random tail like
    :func:`build_kv_ops`, so chain discovery and gcd sharing stay
    exercised under load.  All values are absolute — the same list
    replays bit-identically into any slot engine."""
    from repro.serving.slots import poisson_arrival_ticks

    rng = np.random.default_rng(spec.seed)
    ticks = poisson_arrival_ticks(
        spec.n_requests, rate=spec.rate, seed=spec.seed,
        burst_frac=spec.burst_frac, silence_ticks=spec.silence_ticks)
    shared = list(rng.integers(0, spec.key_space, size=spec.shared_pool))
    out: List[Tuple] = []
    lo = max(1, spec.min_prompt)
    hi = max(lo + 1, spec.max_prompt)
    for i, t in enumerate(ticks):
        n = int(rng.integers(lo, hi))
        pfx = int(rng.integers(0, min(spec.shared_pool, n) + 1))
        tail = [int(x) for x in rng.integers(0, spec.key_space,
                                             size=n - pfx)]
        out.append((int(t), tuple(shared[:pfx] + tail),
                    int(rng.integers(1, max(2, spec.max_new))),
                    int(rng.integers(spec.n_tenants))
                    if spec.n_tenants > 1 else 0))
    return out


def drive_slots(engine, arrivals: Sequence[Tuple], schedule=None,
                on_event=None, step_hook=None,
                max_ticks: int = 100_000) -> List[str]:
    """Submit an arrival trace into a slot engine and tick it to idle;
    returns the engine's full tier log (the differential-comparison
    payload).  ``schedule`` (a :func:`build_failure_schedule` dict:
    tick index -> event list) injects chaos events against the
    engine's page cache BEFORE the step at that tick, exactly as
    :func:`apply_kv_ops` does per op — the elastic x batching
    composition fuzz.  ``step_hook(engine)``, when given, runs after
    every tick (the tenancy fuzz proves isolation at each one)."""
    for arrival, prompt, max_new, tenant in arrivals:
        engine.submit(list(prompt), max_new_tokens=max_new,
                      tenant=tenant, arrival=arrival)
    fire = on_event if on_event is not None else apply_elastic_event
    for _ in range(max_ticks):
        if engine.idle():
            return engine.tier_log
        if schedule:
            for ev in schedule.get(engine.now, ()):
                fire(engine.pages, ev)
        engine.step()
        if step_hook is not None:
            step_hook(engine)
    raise RuntimeError(f"slot engine failed to drain within "
                       f"{max_ticks} ticks")


def arrival_specs():
    """Strategy over open-loop arrival specs, biased toward the edges
    the batching parity suite cares about: all-short vs all-long prompt
    mixes, burst-then-silence traffic, ragged decode demands, multi-
    tenant tags (degenerate 1-slot engines and preemption pressure come
    from the caller's engine config)."""
    return st.builds(
        ArrivalSpec,
        seed=st.integers(min_value=0, max_value=2**16),
        n_requests=st.integers(min_value=2, max_value=28),
        rate=st.sampled_from([0.3, 1.5, 6.0]),
        burst_frac=st.sampled_from([0.0, 0.5, 1.0]),
        silence_ticks=st.sampled_from([0, 12]),
        min_prompt=st.sampled_from([1, 6, 40]),
        max_prompt=st.sampled_from([6, 24, 90]),
        max_new=st.sampled_from([2, 10, 24]),
        shared_pool=st.sampled_from([4, 16]),
        key_space=st.sampled_from([60, 200]),
        n_tenants=st.sampled_from([1, 2]),
    )


# --------------------------------------------------------------------------- #
# MoE expert workloads (serving tier)                                         #
# --------------------------------------------------------------------------- #

@dataclass(frozen=True)
class ExpertWorkloadSpec:
    """Compact description of a router-driven expert workload; expanded
    by :func:`build_expert_sets` into per-step batches of top-k sets."""

    seed: int = 0
    n_experts: int = 32
    n_steps: int = 60
    batch: int = 4                 # router sets per decode step
    group_size: int = 4            # top-k draw size (> max_group hits the cap)
    n_groups: int = 12             # co-activation pool size
    zipf_a: float = 1.0            # expert-popularity skew of group draws
    disjoint: bool = False         # adversarial: groups partition the experts
    repeat_hot: bool = False       # adversarial: one group dominates
    oversize_every: int = 0        # every k-th step adds a fresh oversized
    #                                draw (cap-collision / dedup edges)


def build_expert_sets(spec: ExpertWorkloadSpec) -> List[List[Tuple[int, ...]]]:
    """Expand a spec into per-decode-step batches of router top-k sets.

    The same concrete sets drive every cache implementation (expert ids
    are absolute, not selectors: the expert universe is fixed at
    construction), so two bit-equal caches see bit-equal streams.
    """
    rng = np.random.default_rng(spec.seed)
    k = max(1, min(spec.group_size, spec.n_experts))
    if spec.disjoint:
        perm = rng.permutation(spec.n_experts)
        pool = [tuple(int(e) for e in perm[i:i + k])
                for i in range(0, spec.n_experts - k + 1, k)]
        pool = pool[:max(1, spec.n_groups)] or [tuple(range(k))]
    else:
        pop = 1.0 / np.arange(1, spec.n_experts + 1) ** spec.zipf_a
        pop /= pop.sum()
        pool = [tuple(int(e) for e in rng.choice(
            spec.n_experts, size=k, replace=False, p=pop))
            for _ in range(max(1, spec.n_groups))]
    steps: List[List[Tuple[int, ...]]] = []
    for t in range(spec.n_steps):
        sets = []
        for _ in range(spec.batch):
            if spec.repeat_hot and rng.integers(2) == 0:
                sets.append(pool[0])
            else:
                sets.append(pool[int(rng.integers(len(pool)))])
        if spec.oversize_every and t % spec.oversize_every == 0:
            big = min(spec.n_experts, 2 * k + 1)
            sets.append(tuple(int(e) for e in rng.choice(
                spec.n_experts, size=big, replace=False)))
        steps.append(sets)
    return steps


def drive_expert(ec, step_batches: Sequence[Sequence[Tuple[int, ...]]]
                 ) -> List[Tuple]:
    """Replay per-step router batches against one expert cache — each
    step is ONE ``observe_routing`` + ONE ``activate_batch`` call, the
    serving engine's exact calling convention; returns every per-set
    tier decision (the differential-comparison payload)."""
    tiers: List[Tuple] = []
    for batch in step_batches:
        ec.observe_routing(batch)
        for t in ec.activate_batch(batch):
            tiers.append(tuple(sorted(t.items())))
    return tiers


def expert_workload_specs():
    """Strategy over expert workload specs, biased toward the parity
    edges: skewed popularity, adversarial repeated-group and
    disjoint-partition schedules, oversized draws that overflow
    ``max_group`` (degenerate 1-slot HBM comes from the caller's cache
    config)."""
    return st.builds(
        ExpertWorkloadSpec,
        seed=st.integers(min_value=0, max_value=2**16),
        n_experts=st.sampled_from([4, 16, 48]),
        n_steps=st.integers(min_value=5, max_value=60),
        batch=st.integers(min_value=1, max_value=6),
        group_size=st.integers(min_value=2, max_value=12),
        n_groups=st.sampled_from([2, 8, 24]),
        zipf_a=st.sampled_from([0.0, 1.0, 1.6]),
        disjoint=st.booleans(),
        repeat_hot=st.booleans(),
        oversize_every=st.sampled_from([0, 3]),
    )


# --------------------------------------------------------------------------- #
# multi-limb composite universes (wide-registry tier, DESIGN.md §11)          #
# --------------------------------------------------------------------------- #

@dataclass(frozen=True)
class LimbUniverseSpec:
    """Compact description of a squarefree-composite universe over a
    prime pool; expanded by :func:`build_limb_universe` into concrete
    exact Python ints — the limb-kernel differential fuzz's input
    (tests/test_limbs.py)."""

    seed: int = 0
    n_pool: int = 64               # prime pool size
    n_composites: int = 24
    max_factors: int = 20          # factors per composite (chain depth)
    max_bits: int = 256            # registry chunk width under test
    big_primes: bool = True        # draw near the 25-bit kernel prime cap


def build_limb_universe(spec: LimbUniverseSpec):
    """Expand a spec into ``(pool, composites)``: a sorted prime pool
    and squarefree products of pool subsets, every product strictly
    under ``2**spec.max_bits``.  All values are exact Python ints — the
    oracle side of the differential fuzz; the kernel side packs them
    with :func:`repro.core.composite.pack_limbs`."""
    from repro.core.primes import segmented_sieve, sieve_primes

    rng = np.random.default_rng(spec.seed)
    small = [int(p) for p in sieve_primes(10_000)[5:]]
    pool = set(small[: spec.n_pool])
    if spec.big_primes:
        # the top of the kernel prime range (primes < 2**25, DESIGN.md §3)
        from repro.core.primes import MAX_PRIME_LIMIT
        lo = MAX_PRIME_LIMIT - 20_000
        pool |= {int(p) for p in segmented_sieve(lo, MAX_PRIME_LIMIT)}
        pool |= {1_000_003, 999_983, 104_729, 15_485_863}
    pool = sorted(pool)
    comps = []
    for _ in range(spec.n_composites):
        k = int(rng.integers(1, spec.max_factors + 1))
        ps = rng.choice(len(pool), size=min(k, len(pool)), replace=False)
        c = 1
        for i in ps:
            nxt = c * pool[int(i)]
            if nxt.bit_length() >= spec.max_bits:
                break
            c = nxt
        if c > 1:
            comps.append(c)
    return pool, (comps or [pool[0] * pool[1]])


def limb_universe_specs():
    """Strategy over limb-universe specs, biased toward the edges the
    digit kernels care about: one-word vs many-digit widths, chains
    deep enough to span many digits, primes adjacent to the 25-bit
    cap."""
    return st.builds(
        LimbUniverseSpec,
        seed=st.integers(min_value=0, max_value=2**16),
        n_pool=st.sampled_from([8, 64, 200]),
        n_composites=st.integers(min_value=1, max_value=32),
        max_factors=st.sampled_from([2, 8, 40]),
        max_bits=st.sampled_from([64, 96, 256, 1024]),
        big_primes=st.booleans(),
    )


# --------------------------------------------------------------------------- #
# simulator traces (engine tier)                                              #
# --------------------------------------------------------------------------- #

def trace_zoo(length: int, seeds: Sequence[int] = (1, 2)) -> list:
    """The engine suite's standard covering set: skewed zipf traffic,
    relationship-rich db joins, and the LRU-adversarial sequential
    scan."""
    from repro.core import db_join_trace, scan_trace, zipf_trace

    return [
        zipf_trace(n_keys=400, n_accesses=length, seed=seeds[0]),
        db_join_trace(n_orders=150, n_customers=40, n_items=80,
                      n_queries=length, seed=seeds[1]),
        scan_trace(n_keys=length // 3, n_passes=3),
    ]


def make_trace(kind: str, length: int, seed: int):
    """One trace by kind — the expansion target of :func:`trace_specs`."""
    from repro.core import (db_join_trace, graph_walk_trace, scan_trace,
                            zipf_trace)

    if kind == "zipf":
        return zipf_trace(n_keys=300, n_accesses=length, seed=seed)
    if kind == "db":
        return db_join_trace(n_orders=120, n_customers=30, n_items=60,
                             n_queries=length, seed=seed)
    if kind == "graph":
        return graph_walk_trace(n_keys=250, relationship_density=0.6,
                                n_accesses=length, seed=seed)
    if kind == "scan":
        return scan_trace(n_keys=max(4, length // 3), n_passes=3)
    if kind == "adversarial":
        return adversarial_trace(length=length, seed=seed)
    raise ValueError(f"unknown trace kind {kind!r}")


def adversarial_trace(length: int = 1200, capacity: int = 96,
                      seed: int = 0, hot_keys: int = 8):
    """Eviction-adversarial access stream: cyclic sweeps over a working
    set one larger than the given capacity (every access misses under
    plain LRU of that size) interleaved with a small reused hot set —
    the recency-thrash pattern scan-resistant policies (2Q/ARC/LIRS)
    exist to survive."""
    from repro.core.traces import Trace

    rng = np.random.default_rng(seed)
    sweep_keys = capacity + 1
    acc = []
    pos = 0
    for _ in range(length):
        if rng.integers(4) == 0:
            acc.append(sweep_keys + int(rng.integers(hot_keys)))
        else:
            acc.append(pos % sweep_keys)
            pos += 1
    return Trace(name=f"adversarial[{capacity}]",
                 accesses=np.asarray(acc, dtype=np.int64),
                 relationships=[], n_keys=sweep_keys + hot_keys,
                 meta={"kind": "adversarial"})


def trace_specs():
    """Strategy over (kind, length, seed) simulator-trace specs."""
    return st.tuples(
        st.sampled_from(["zipf", "db", "graph", "scan", "adversarial"]),
        st.integers(min_value=64, max_value=900),
        st.integers(min_value=0, max_value=2**16),
    )


def adversarial_stream_specs():
    """Strategy over eviction-adversarial stream parameters."""
    return st.tuples(
        st.integers(min_value=64, max_value=600),    # length
        st.sampled_from([4, 16, 96]),                # thrashed capacity
        st.integers(min_value=0, max_value=2**16),   # seed
    )
