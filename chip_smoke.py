#!/usr/bin/env python
"""On-chip smoke run of the PFCS serving path, through its normal entry
points, in one process.

Phases (default: one TPU chip):

  (a) device   — fail unless JAX's default backend is ``tpu``.
  (b) serve    — full-width ``qwen2.5-3b`` (random weights from
                 ``--seed``) served by ``ServingEngine`` with the PFCS
                 ``kv="vec"`` page cache: 8 requests sharing a 64-token
                 prefix, ``max_batch`` 8, ``max_new`` 32.  Every logit is
                 finite, every request completes with ``max_new`` tokens,
                 and the scalar ``PagedKVCache`` oracle fed the same
                 request stream agrees on every ``PARITY_COUNTERS``
                 counter.
  (c) kernels  — the discovery kernels compiled on the chip: the
                 null-model ``SlotMachine`` serves 1,024 requests on 128
                 slots next to its scalar ``SlotOracle``; during the run
                 the successor table is rebuilt with
                 ``refresh_tables(discover="kernel")`` and every rebuild
                 must equal the incremental rows, and ``shared_prefix_bulk``
                 over live request pairs must equal the oracle's
                 ``shared_prefix``.  Run once narrow (62-bit registry)
                 and once wide (``max_bits`` 128).

``--four-chips`` runs only the sharded phase on one host's four chips:
``kv="sharded"`` at 4 shards under ``shard_map`` against ``kv="vec"`` on
the same stream, then one ``fail_shard`` and recovery through
``ServingEngine(kv="elastic")``, with parity after it.

Each phase prints its compile and wall seconds: smoke timings, not
benchmark numbers.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

    python chip_smoke.py [--seed N] [--four-chips]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2.5-3b"


class CompileClock:
    """Sums XLA backend-compile seconds reported by JAX's monitoring."""

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name.endswith("backend_compile_duration"):
            self.total += secs


def report(phase: str, clock: CompileClock, c0: float, t0: float, **facts):
    extra = " ".join(f"{k}={v}" for k, v in facts.items())
    print(f"[smoke timing, not a benchmark] {phase}: "
          f"compile_s={clock.total - c0} wall_s={time.perf_counter() - t0} "
          f"{extra}", flush=True)


# --------------------------------------------------------------------------- #
# (b) full-width serve                                                        #
# --------------------------------------------------------------------------- #

def serve_prompts(rng, vocab: int, n: int, shared: int):
    prefix = [int(t) for t in rng.integers(0, vocab, size=shared)]
    return [prefix + [int(t) for t in rng.integers(0, vocab, size=8)]
            for _ in range(n)]


def phase_serve(seed: int, smoke_config: bool = False, n_requests: int = 8,
                shared: int = 64, max_new: int = 32, max_seq: int = 2048):
    import jax.numpy as jnp

    from repro.launch.serve import build_model
    from repro.serving.engine import ServingEngine

    model, params, vocab = build_model(ARCH, smoke_config, seed)
    prompts = serve_prompts(np.random.default_rng(seed), vocab, n_requests,
                            shared)

    engine = ServingEngine(model, params, max_batch=n_requests,
                           max_seq=max_seq, kv="vec")
    finite = []
    decode = engine._decode

    def checked_decode(p, batch, cache):
        out = decode(p, batch, cache)
        assert out[0].shape == (n_requests, 1, vocab), out[0].shape
        finite.append(jnp.isfinite(out[0]).all())
        return out

    engine._decode = checked_decode
    oracle = ServingEngine(None, None, max_batch=n_requests,
                           max_seq=max_seq, kv="scalar")
    for prompt in prompts:
        engine.submit(prompt, max_new_tokens=max_new)
        oracle.submit(prompt, max_new_tokens=max_new)
    done = engine.run_until_idle()
    oracle.run_until_idle()

    assert len(done) == n_requests, len(done)
    assert all(len(r.generated) == max_new for r in done)
    assert finite and all(bool(f) for f in finite), "non-finite logits"
    got, want = engine.pages.stats.parity_tuple(), \
        oracle.pages.stats.parity_tuple()
    assert got == want, f"vec {got} != scalar oracle {want}"
    assert engine.pages.stats.shared_prefix_pages > 0
    return {"requests": len(done), "decode_calls": len(finite),
            "parity": list(got)}


# --------------------------------------------------------------------------- #
# (c) discovery kernels under the slot machine                                #
# --------------------------------------------------------------------------- #

def slot_stream(rng, n: int, n_prefixes: int = 16):
    prefixes = [[int(t) for t in rng.integers(0, 32_000,
                                              size=rng.integers(64, 257))]
                for _ in range(n_prefixes)]
    out = []
    for _ in range(n):
        pre = prefixes[int(rng.integers(0, n_prefixes))]
        tail = [int(t) for t in rng.integers(0, 32_000,
                                             size=rng.integers(8, 97))]
        out.append((pre + tail, int(rng.integers(8, 33))))
    return out


def phase_kernels(seed: int, max_bits: int, n_requests: int = 1024,
                  max_batch: int = 128, check_every: int = 16,
                  max_pairs: int = 64):
    from repro.kernels.ops import interpret_mode
    from repro.obs import profile
    from repro.serving.slots import (SlotMachine, SlotOracle,
                                     poisson_arrival_ticks)

    rng = np.random.default_rng(seed)
    kw = dict(max_batch=max_batch, hbm_pages=4 * max_batch, page_size=16,
              prefill_tokens=512, max_bits=max_bits)
    machine = SlotMachine(kv="vec", **kw)
    oracle = SlotOracle(kv="scalar", **kw)
    stream = slot_stream(rng, n_requests)
    arrivals = poisson_arrival_ticks(n_requests, 4.0, seed=seed)
    for (prompt, max_new), tick in zip(stream, arrivals):
        machine.submit(prompt, max_new_tokens=max_new, arrival=int(tick))
        oracle.submit(prompt, max_new_tokens=max_new, arrival=int(tick))

    interpreted = interpret_mode()
    rebuilds = pairs = shared_pairs = max_rows = 0
    with profile.profiling():
        while not (machine.idle() and oracle.idle()):
            machine.step()
            oracle.step()
            if machine.ticks % check_every:
                continue
            pages = machine.pages
            before = pages.successor_rows()
            pages.refresh_tables(discover="kernel")
            assert pages.successor_rows() == before, \
                f"kernel rebuild diverged at tick {machine.ticks}"
            rebuilds += 1
            max_rows = max(max_rows, len(before))
            live = sorted(pages.chains)
            pair_list = list(zip(live, live[1:]))[:max_pairs]
            bulk = pages.shared_prefix_bulk(pair_list)
            for a, b in pair_list:
                assert bulk[(a, b)] == oracle.pages.shared_prefix(a, b), \
                    f"shared_prefix({a}, {b}) diverged"
                shared_pairs += bool(bulk[(a, b)])
            pairs += len(pair_list)
        launches = profile.summary()

    done = [r for r in machine.requests if r.state == "done"]
    assert len(done) == n_requests, len(done)
    got, want = machine.pages.stats.parity_tuple(), \
        oracle.pages.stats.parity_tuple()
    assert got == want, f"vec {got} != scalar oracle {want}"
    assert rebuilds and pairs and shared_pairs, (rebuilds, pairs,
                                                 shared_pairs)
    for name in ("divisibility_scan", "factorize_batch", "gcd_batch"):
        assert launches.get(name, {}).get("calls"), f"{name} never ran"
    return {"interpret": interpreted, "ticks": machine.ticks,
            "kernel_rebuilds": rebuilds, "max_table_rows": max_rows,
            "pairs_checked": pairs, "pairs_sharing": shared_pairs,
            "kernel_calls": {k: v["calls"] for k, v in launches.items()},
            "parity": list(got)}


# --------------------------------------------------------------------------- #
# four chips: sharded and elastic caches                                      #
# --------------------------------------------------------------------------- #

def watch_refreshes(pages):
    """Record ``last_scan.used_shard_map`` after every sharded table
    refresh of ``pages`` (one entry per ``bulk_refreshes`` increment)."""
    seen = []
    refresh = pages.refresh_tables

    def checked(discover=None):
        refresh(discover)
        seen.append(pages.last_scan.used_shard_map)

    pages.refresh_tables = checked
    return seen


def phase_four_chips(seed: int, n_shards: int = 4, n_requests: int = 256,
                     max_batch: int = 64):
    import jax

    from repro.serving.engine import ServingEngine

    stream = slot_stream(np.random.default_rng(seed), n_requests)

    def engine(kv, **kw):
        e = ServingEngine(None, None, max_batch=max_batch, kv=kv,
                          page_size=16, hbm_pages=4 * max_batch, **kw)
        for prompt, max_new in stream:
            e.submit(prompt, max_new_tokens=max_new)
        return e

    vec = engine("vec")
    vec.run_until_idle()
    want = vec.pages.stats.parity_tuple()

    sharded = engine("sharded", shards=n_shards)
    assert sharded.pages.mesh is not None, "no 4-device mesh"
    scans = watch_refreshes(sharded.pages)
    sharded.run_until_idle()
    refreshes = sharded.pages.bulk_refreshes
    got = sharded.pages.stats.parity_tuple()
    assert refreshes and len(scans) == refreshes, (len(scans), refreshes)
    assert all(scans), "a sharded refresh bypassed shard_map"
    assert got == want, f"sharded {got} != vec {want}"

    elastic = engine("elastic", shards=n_shards)
    scans_e = watch_refreshes(elastic.pages)
    for _ in range(len(stream) // (2 * max_batch) + 8):
        elastic.step()
    recovery = elastic.fail_shard(1)
    elastic.run_until_idle()
    refreshes_e = elastic.pages.bulk_refreshes
    got_e = elastic.pages.stats.parity_tuple()
    assert refreshes_e and len(scans_e) == refreshes_e, (len(scans_e),
                                                         refreshes_e)
    assert all(scans_e), "an elastic refresh bypassed shard_map"
    assert got_e == want, f"elastic {got_e} != vec {want}"

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:n_shards]]
    assert all(p > 0 for p in peaks), f"idle device in the mesh: {peaks}"
    return {"shards": n_shards, "sharded_refreshes": refreshes,
            "elastic_refreshes": refreshes_e,
            "used_shard_map": all(scans) and all(scans_e),
            "parity": list(got),
            "recovery": {"mode": recovery.mode,
                         "refactorized": recovery.refactorized},
            "peak_bytes_per_device": peaks}


# --------------------------------------------------------------------------- #

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for weights, prompts and arrivals")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded/elastic phase on 4 chips")
    args = ap.parse_args(argv)

    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not importable: {e}",
              file=sys.stderr)
        return 2
    enable_compile_cache()

    import jax

    # (a) device
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's default backend is "
              f"{backend!r}", file=sys.stderr)
        return 1
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"device: {device['kind']} x {device['count']}", flush=True)
    clock = CompileClock()

    if args.four_chips:
        if len(devices) < 4:
            print(f"chip_smoke: --four-chips needs 4 devices, found "
                  f"{len(devices)}", file=sys.stderr)
            return 1
        c0, t0 = clock.total, time.perf_counter()
        res = phase_four_chips(args.seed)
        report("four_chips", clock, c0, t0, result=json.dumps(res))
    else:
        c0, t0 = clock.total, time.perf_counter()
        res = phase_serve(args.seed)
        report(f"serve[{ARCH}]", clock, c0, t0, result=json.dumps(res))
        for max_bits in (62, 128):
            c0, t0 = clock.total, time.perf_counter()
            res = phase_kernels(args.seed, max_bits)
            assert not res["interpret"], "kernels ran in interpret mode"
            report(f"kernels[max_bits={max_bits}]", clock, c0, t0,
                   result=json.dumps(res))

    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
