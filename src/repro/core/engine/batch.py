"""Engine drivers: ``lax.scan`` over a trace, ``vmap`` over a batch.

One compiled function serves every trace of the same (length, key-space,
capacity-config) signature; builders are memoized on those static
parameters.  Batching stacks traces on a leading axis and ``vmap``s the
whole scan — per-trace PFCS tables ride along as batched inputs, and
shorter traces are padded with key ``-1`` (an exact no-op step), so
ragged batches lose nothing.

The compiled scans run under ``jax.enable_x64``: all state is explicitly
int32 (DESIGN.md §3) except ARC's float64 adaptive target, which must
match the CPython float arithmetic of the oracle bit-for-bit.  Discovery
tables are built before that context is entered, so no Pallas kernel
ever runs under x64.

``AccessStats`` assembly mirrors the scalar simulators field-for-field,
so callers (benchmarks, Table 1 derivations) cannot tell which engine
produced a result — except by wall clock.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..metrics import AccessStats
from ..traces import Trace
from .layout import tree_where
from .pfcs_vec import build_pfcs
from .policies_vec import POLICY_TICKS
from .tables import PFCSTables, pfcs_tables

__all__ = ["simulate_trace", "simulate_batch", "sweep", "VECTORIZED_SYSTEMS"]

#: systems the engine can simulate (the semantic baseline stays scalar —
#: its RNG noise is consumed in miss order, which is inherently serial)
VECTORIZED_SYSTEMS = ("lru", "fifo", "2q", "arc", "lirs", "pfcs")

_DEFAULT_LEVELS = (("L1", 64), ("L2", 512), ("L3", 4096))


# --------------------------------------------------------------------------- #
# compiled cores (memoized per static signature)                              #
# --------------------------------------------------------------------------- #

@functools.lru_cache(maxsize=None)
def _baseline_core(policy: str, caps: Tuple[Tuple[str, int], ...],
                   n_keys: int, length: int, batched: bool):
    import jax
    import jax.numpy as jnp

    from .hierarchy import build_hierarchy

    n_levels = len(caps)

    def run(accesses):
        state, step = build_hierarchy(policy, caps, n_keys)

        def body(carry, inp):
            s, hits, miss, demand = carry
            key, t = inp
            valid = key >= 0
            s2, (hit, tier) = step(s, jnp.maximum(key, 0),
                                   t * POLICY_TICKS)
            s2 = tree_where(valid, s2, s)
            hit = hit & valid
            onehot = (jnp.arange(n_levels + 1, dtype=jnp.int32) == tier) & hit
            return (s2, hits + onehot, miss + (valid & ~hit),
                    demand + valid), ()

        init = (state,
                jnp.zeros((n_levels + 1,), jnp.int32),
                jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
        ts = jnp.arange(length, dtype=jnp.int32)
        (_, hits, miss, demand), _ = jax.lax.scan(body, init, (accesses, ts))
        return {"hits": hits, "miss": miss, "demand": demand}

    return jax.jit(jax.vmap(run) if batched else run)


@functools.lru_cache(maxsize=None)
def _pfcs_core(caps: Tuple[Tuple[str, int], ...], n_keys: int,
               budget: int, window: int, enable_pf: bool, always: bool,
               length: int, batched: bool):
    import jax
    import jax.numpy as jnp

    def run(accesses, tgt, truth, deg):
        state, micro, step = build_pfcs(caps, n_keys, budget, window,
                                        enable_pf, always)

        def body(s, inp):
            key, t = inp
            return step(s, key, t * micro, tgt, truth, deg), ()

        ts = jnp.arange(length, dtype=jnp.int32)
        s, _ = jax.lax.scan(body, state, (accesses, ts))
        return s["stats"]

    return jax.jit(jax.vmap(run) if batched else run)


# --------------------------------------------------------------------------- #
# AccessStats assembly                                                        #
# --------------------------------------------------------------------------- #

def _baseline_stats(policy: str, caps, out, i: Optional[int]) -> AccessStats:
    pick = (lambda x: np.asarray(x)[i]) if i is not None else np.asarray
    hits = pick(out["hits"])
    st = AccessStats(name=policy.upper())
    st.hits_per_level = {name: int(h) for (name, _), h in zip(caps, hits)}
    st.hits_per_level["MEM"] = int(hits[len(caps)])
    st.misses = int(pick(out["miss"]))
    st.demand_accesses = int(pick(out["demand"]))
    return st


def _pfcs_stats(caps, out, tables: PFCSTables, i: Optional[int]) -> AccessStats:
    pick = (lambda x: np.asarray(x)[i]) if i is not None else np.asarray
    hits = pick(out["hits"])
    st = AccessStats(name="PFCS")
    st.hits_per_level = {name: int(h) for (name, _), h in zip(caps, hits)}
    st.misses = int(pick(out["miss"]))
    st.demand_accesses = int(pick(out["demand"]))
    st.prefetches_issued = int(pick(out["issued"]))
    st.prefetches_used = int(pick(out["used"]))
    st.prefetches_true = int(pick(out["true"]))
    st.extra_backing_fetches = st.prefetches_issued
    st.factor_ops = dict(tables.factor_ops)
    return st


# --------------------------------------------------------------------------- #
# public drivers                                                              #
# --------------------------------------------------------------------------- #

def _key_space(traces: Sequence[Trace]) -> int:
    return max(max(tr.n_keys, int(tr.accesses.max(initial=0)) + 1)
               for tr in traces)


def simulate_trace(trace: Trace, system: str,
                   capacities: Sequence[Tuple[str, int]] = _DEFAULT_LEVELS,
                   *, prefetch_budget: int = 4, victim_window: int = 8,
                   enable_prefetch: bool = True,
                   prefetch_trigger: str = "miss",
                   discover: str = "host",
                   tables: Optional[PFCSTables] = None) -> AccessStats:
    """Simulate ONE trace on the vectorized engine -> AccessStats.

    Bit-identical to ``simulate_baseline(system, trace, capacities)`` /
    ``simulate_pfcs(trace, capacities, ...)`` on every counter the
    scalar oracles produce (see tests/test_engine.py).
    """
    return simulate_batch([trace], system, capacities,
                          prefetch_budget=prefetch_budget,
                          victim_window=victim_window,
                          enable_prefetch=enable_prefetch,
                          prefetch_trigger=prefetch_trigger,
                          discover=discover,
                          tables=[tables] if tables is not None else None)[0]


def simulate_batch(traces: Sequence[Trace], system: str,
                   capacities: Sequence[Tuple[str, int]] = _DEFAULT_LEVELS,
                   *, prefetch_budget: int = 4, victim_window: int = 8,
                   enable_prefetch: bool = True,
                   prefetch_trigger: str = "miss",
                   discover: str = "host",
                   tables: Optional[Sequence[PFCSTables]] = None,
                   ) -> List[AccessStats]:
    """Simulate a batch of traces in ONE ``vmap``-batched scan.

    Traces may have ragged lengths (padded with no-op steps) and ragged
    key spaces (state sized to the largest).  Returns one
    ``AccessStats`` per trace, in order.
    """
    import jax
    import jax.numpy as jnp

    system = system.lower()
    if system not in VECTORIZED_SYSTEMS:
        raise ValueError(f"engine cannot simulate {system!r}; "
                         f"supported: {VECTORIZED_SYSTEMS}")
    caps = tuple((str(n), int(c)) for n, c in capacities)
    n = len(traces)
    length = max(tr.length for tr in traces)
    n_keys = _key_space(traces)
    # int32 stamp bound (layout.py): each access consumes a fixed stride
    # of micro-op ticks; past 2**31 stamps would wrap into the negative
    # init-stamp range and silently corrupt recency order — fail instead
    ticks = (len(caps) + max(1, int(prefetch_budget))
             if system == "pfcs" else POLICY_TICKS)
    if length * ticks >= 2**31:
        raise ValueError(
            f"trace length {length} x {ticks} stamp ticks/access exceeds "
            f"the engine's int32 stamp space ({2**31 - 1}); split the "
            f"trace into <= {(2**31 - 1) // ticks}-access segments")
    acc = np.full((n, length), -1, dtype=np.int32)
    for i, tr in enumerate(traces):
        acc[i, :tr.length] = np.asarray(tr.accesses, dtype=np.int32)
    batched = n > 1

    if system == "pfcs":
        budget = max(1, int(prefetch_budget))
        if tables is not None:
            # caller-built tables define the key universe (targets may
            # index keys the residency array must be able to hold)
            sizes = {tb.targets.shape[0] for tb in tables}
            if len(sizes) > 1:
                raise ValueError(f"tables disagree on key-space size: "
                                 f"{sorted(sizes)}")
            if max(sizes) < n_keys:
                raise ValueError(
                    f"tables cover {max(sizes)} keys but the traces "
                    f"reach key {n_keys - 1}; rebuild with n_keys>="
                    f"{n_keys}")
            n_keys = max(sizes)
            if any(tb.targets.shape[1] != budget for tb in tables):
                raise ValueError(
                    f"tables built for budget "
                    f"{tables[0].targets.shape[1]}, run requested "
                    f"{budget}; rebuild with matching prefetch_budget")
        else:
            # discovery (possibly through the 32-bit Pallas kernels)
            # happens here, outside the x64 context below
            tables = [pfcs_tables(tr, caps, prefetch_budget,
                                  victim_window, enable_prefetch,
                                  prefetch_trigger, discover,
                                  n_keys=n_keys)
                      for tr in traces]
        tgt = np.stack([tb.targets for tb in tables])
        truth = np.stack([tb.truth for tb in tables])
        deg = np.stack([tb.degree for tb in tables])
        if not batched:
            tgt, truth, deg = tgt[0], truth[0], deg[0]
        with jax.enable_x64(True):
            fn = _pfcs_core(caps, n_keys, budget, int(victim_window),
                            bool(enable_prefetch),
                            prefetch_trigger == "always", length, batched)
            out = fn(jnp.asarray(acc if batched else acc[0]),
                     jnp.asarray(tgt), jnp.asarray(truth), jnp.asarray(deg))
        return [_pfcs_stats(caps, out, tables[i],
                            i if batched else None) for i in range(n)]

    # only LIRS carries per-key state; every other policy's compiled
    # core is key-space independent — normalize the cache key so one
    # compile serves traces of any key universe
    pol_keys = n_keys if system == "lirs" else 0
    with jax.enable_x64(True):
        fn = _baseline_core(system, caps, pol_keys, length, batched)
        out = fn(jnp.asarray(acc if batched else acc[0]))
    return [_baseline_stats(system, caps, out, i if batched else None)
            for i in range(n)]


def sweep(traces: Sequence[Trace], systems: Sequence[str],
          capacity_configs: Sequence[Sequence[Tuple[str, int]]],
          **kw) -> Dict[Tuple[str, int], List[AccessStats]]:
    """Systems x capacity-configs x traces sweep.

    Returns ``{(system, config_index): [AccessStats per trace]}``.  Each
    (system, config) cell is one vmap-batched run over all traces —
    capacity configs compile separately (shapes differ), traces batch.
    """
    out: Dict[Tuple[str, int], List[AccessStats]] = {}
    for ci, caps in enumerate(capacity_configs):
        for system in systems:
            out[(system, ci)] = simulate_batch(traces, system, caps, **kw)
    return out
