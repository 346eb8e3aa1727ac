"""Run one benchmark cell once, on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Cells, metrics and bounds are in ``BENCHMARK.json`` at the root of the
checkout.  The run fails, and prints no result, unless JAX's backend is
``tpu`` with as many chips as the cell asks for.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or its per-layer
metrics with ``--trace 1``), ``device``, ``breakdown`` (traced runs) and
``checked``, every number compared with its limit.  The same numbers are
the last lines of standard error.

``--control 1`` runs the control instead of the program as configured:
the successor table is rebuilt on one tick in eight only, which the
comparison has to refuse.  The benchmark's own runs never set it.
"""

from __future__ import annotations

import time

_STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # JAX's persistent compilation cache stays at a fixed path in the
    # checkout, so every run of a cell after the first finds its programs
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(_ROOT / ".jax_cache")

    from chipbench.harness import run

    out = run(args.workload, args.seed, args.seconds, bool(args.trace),
              started=_STARTED, control=bool(args.control))
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
