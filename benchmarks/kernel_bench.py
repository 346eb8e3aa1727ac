"""Kernel throughput: Pallas factorization/scan/gcd (interpret mode on this
CPU container — wall numbers are correctness-path timings, the TPU story
is the roofline) + host Factorizer stage mix."""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import Factorizer, sieve_primes
from repro.kernels.ops import divisibility_scan, factorize_batch, gcd_batch

from .common import emit, save_bench, save_json, timed


def squarefree_pairs(rng, primes, n):
    """``n`` products of two distinct ``primes`` (the registry's
    squarefree invariant, which the pool gcd needs)."""
    return [int(p) * int(q) for p, q in
            (rng.choice(primes, size=2, replace=False) for _ in range(n))]


def run_smoke():
    """Tiny kernel pass under the launch-ledger profiler (DESIGN.md
    §13).  The checked-in ``BENCH_kernel_bench.json`` payload is ONLY
    the wall-clock-exempt ``obs`` block — every number in it (walls,
    and calls/items, which track jit cache state) is reporting, not a
    gated deterministic contract; the regression gate skips the whole
    block by its ``obs`` component."""
    from repro.obs import profile

    rng = np.random.default_rng(0)
    primes = sieve_primes(10_000)
    pool = primes[100:100 + 256].astype(np.int64)
    pairs = rng.choice(primes[100:], size=(256, 2), replace=True)
    comps = (pairs[:, 0] * pairs[:, 1]).astype(np.int64)
    with profile.profiling():
        factorize_batch(list(comps), list(pool))
        divisibility_scan(list(comps), list(pool[:64]))
        sq = squarefree_pairs(rng, primes[100:], 256)
        gcd_batch(sq, sq[::-1], list(primes[100:]))
    launches = profile.summary()
    print("\n== kernels (smoke, launch ledger) ==")
    for name, rec in sorted(launches.items()):
        print(f"   {name}: {rec['calls']} call(s), {rec['items']} items, "
              f"{rec['wall_s']*1e3:.1f} ms")
        emit(f"kernel.{name}.wall_s", rec["wall_s"] * 1e6,
             f"calls={rec['calls']}")
    out = {"obs": {"kernel_launches": launches}}
    save_json("kernel_bench_smoke", out)
    save_bench("kernel_bench", out)
    return out


def run():
    rng = np.random.default_rng(0)
    primes = sieve_primes(10_000)
    out = {}

    # batched factorization kernel
    pairs = rng.choice(primes[100:], size=(4096, 2), replace=True)
    comps = (pairs[:, 0] * pairs[:, 1]).astype(np.int64)
    pool = primes[100:100 + 1024].astype(np.int64)
    (facs, _), dt = timed(factorize_batch, list(comps), list(pool), repeat=3)
    per = dt / len(comps) * 1e6
    print(f"\n== kernels == factorize_batch: {len(comps)} composites x "
          f"{len(pool)} primes in {dt*1e3:.1f} ms ({per:.2f} us/composite)")
    emit("kernel.factorize_batch.us_per_composite", per)
    out["factorize_us_per_composite"] = per

    # divisibility scan (prefetch path)
    reg = (rng.choice(primes[100:], size=(8192, 2)).prod(axis=1)).astype(np.int64)
    qs = pool[:512]
    _, dt = timed(divisibility_scan, list(reg), list(qs), repeat=3)
    per_q = dt / len(qs) * 1e6
    print(f"   divisibility_scan: {len(reg)} registry x {len(qs)} queries "
          f"in {dt*1e3:.1f} ms ({per_q:.2f} us/query)")
    emit("kernel.divisibility_scan.us_per_query", per_q)
    out["scan_us_per_query"] = per_q

    # gcd of squarefree pairs, rebuilt from the prime pool
    a = squarefree_pairs(rng, pool, 4096)
    b = squarefree_pairs(rng, pool, 4096)
    _, dt = timed(gcd_batch, a, b, list(pool), repeat=3)
    per_g = dt / len(a) * 1e6
    print(f"   gcd_batch: {len(a)} pairs in {dt*1e3:.1f} ms "
          f"({per_g:.3f} us/pair)")
    emit("kernel.gcd_batch.us_per_pair", per_g)
    out["gcd_us_per_pair"] = per_g

    # vectorized trace engine vs scalar oracle (same hit counts by
    # construction — tests/test_engine.py — so this is pure wall clock;
    # both sides pay relationship discovery inside the timed region)
    from repro.core import db_join_trace, simulate_baseline, simulate_pfcs
    from repro.core.engine import simulate_trace

    caps = (("L1", 64), ("L2", 256), ("L3", 1024))
    tr = db_join_trace(n_orders=2000, n_customers=400, n_items=800,
                       n_queries=20000, seed=1)
    print("   -- trace engine (20k-access db_join, scalar vs lax.scan) --")
    for sysname in ("lru", "arc", "pfcs"):
        if sysname == "pfcs":
            _, dt_sc = timed(simulate_pfcs, tr, caps, repeat=1)
        else:
            _, dt_sc = timed(simulate_baseline, sysname, tr, caps, repeat=1)
        simulate_trace(tr, sysname, caps)                      # compile
        _, dt_en = timed(simulate_trace, tr, sysname, caps, repeat=3)
        us_sc = dt_sc / tr.length * 1e6
        us_en = dt_en / tr.length * 1e6
        print(f"   engine.{sysname}: scalar {us_sc:6.2f} us/access, "
              f"vectorized {us_en:6.2f} us/access "
              f"({dt_sc / max(dt_en, 1e-12):.1f}x)")
        emit(f"engine.{sysname}.us_per_access", us_en,
             f"scalar={us_sc:.2f}")
        out[f"engine_{sysname}_us_per_access"] = us_en
        out[f"engine_{sysname}_scalar_us_per_access"] = us_sc

    # host factorizer stage mix (Algorithm 2)
    f = Factorizer()
    small = rng.integers(4, 10**6, size=20000)
    t0 = time.perf_counter()
    for c in small:
        f.factorize(int(c))
    dt_small = (time.perf_counter() - t0) / len(small) * 1e9
    big_pairs = rng.choice(sieve_primes(2_000_000)[78_498:], size=(500, 2))
    bigs = [int(p) * int(q) for p, q in big_pairs]
    t0 = time.perf_counter()
    for c in bigs:
        f.factorize(c)
    dt_big = (time.perf_counter() - t0) / len(bigs) * 1e9
    t0 = time.perf_counter()
    for c in bigs:
        f.factorize(c)                       # cache hits
    dt_cached = (time.perf_counter() - t0) / len(bigs) * 1e9
    print(f"   host factorizer: SPF-table path {dt_small:.0f} ns/op, "
          f"rho path {dt_big:.0f} ns/op, cached {dt_cached:.0f} ns/op")
    print(f"   stage mix: {f.stats.as_dict()}")
    emit("host_factorizer.spf_ns", dt_small)
    emit("host_factorizer.rho_ns", dt_big)
    emit("host_factorizer.cached_ns", dt_cached)
    out.update(spf_ns=dt_small, rho_ns=dt_big, cached_ns=dt_cached,
               stages=f.stats.as_dict())
    save_json("kernel_bench", out)
    return out


if __name__ == "__main__":
    run()
