"""Benchmark entry point — one function per paper table/figure.

Emits ``name,us_per_call,derived`` CSV rows (stdout) plus human-readable
tables; JSON artifacts land in ``artifacts/bench/``.

    PYTHONPATH=src python -m benchmarks.run [--full] [--smoke] [--skip-roofline]
"""

from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale trial counts (slower)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-scale CI subset: Table 1 at reduced scale "
                         "plus the serving load case, the elastic "
                         "resize/recovery chaos case, the MoE "
                         "expert-serving case, the multi-tenant QoS "
                         "case, the continuous-batching Poisson "
                         "load case, and the million-element wide-"
                         "registry scale case (exercises every serving "
                         "hot path and the multi-limb arithmetic on "
                         "every PR)")
    ap.add_argument("--skip-roofline", action="store_true",
                    help="skip the dry-run-artifact roofline table")
    ap.add_argument("--scale", type=float, default=1.0,
                    help="trace-length multiplier for table1/fig2 "
                         "(the vectorized engine handles >=10x)")
    ap.add_argument("--quiet", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="suppress case_scale build progress lines "
                         "(default: quiet under --smoke — the CI path — "
                         "and verbose otherwise)")
    ap.add_argument("--shards", type=int, default=None,
                    help="run case_serving's sharded-cache config at "
                         "exactly N shards (default: sweep 1/2/4, smoke "
                         "2); uses shard_map when the host exposes >= N "
                         "devices (XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N), host loop otherwise")
    args = ap.parse_args(argv)
    shards = (args.shards,) if args.shards else None

    t0 = time.time()
    print("name,us_per_call,derived")

    from . import table1, fig2, cases, kernel_bench

    if args.smoke:
        table1.run(n_trials=1, trace_scale=0.2)
        cases.case_serving(smoke=True, shards=shards)
        cases.case_elastic(smoke=True)
        cases.case_moe(smoke=True)
        cases.case_tenancy(smoke=True)
        cases.case_batching(smoke=True)
        cases.case_scale(smoke=True, quiet=args.quiet)
        cases.case_dedup(smoke=True)
        kernel_bench.run_smoke()
        print(f"\ntotal benchmark wall time: {time.time() - t0:.1f}s")
        return

    table1.run(n_trials=20 if args.full else 4, trace_scale=args.scale)
    fig2.run_fig2a(trace_scale=args.scale)
    fig2.run_fig2b(trace_scale=args.scale)
    cases.case_db()
    cases.case_ml()
    cases.case_hft()
    cases.case_serving(shards=shards)
    cases.case_elastic()
    cases.case_moe()
    cases.case_tenancy()
    cases.case_batching()
    cases.case_scale(quiet=args.quiet)
    cases.case_dedup()
    kernel_bench.run()
    kernel_bench.run_smoke()

    if not args.skip_roofline:
        try:
            from . import roofline
            roofline.run()
        except Exception as e:  # artifacts may not exist yet
            print(f"[roofline skipped: {e}]")

    print(f"\ntotal benchmark wall time: {time.time() - t0:.1f}s")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
