"""Serve a small model with batched requests through the PFCS serving
stack (prefix sharing, table-driven page prefetch).

Two passes: a real smoke-scale model at small batch through the
``ServingEngine`` decode loop, then the null-model load-generator mode
at 128 concurrent slots through the continuous-batching ``SlotMachine``
front-end (DESIGN.md §10) — the serving hot path the load benchmarks
(`benchmarks.cases.case_serving` / `case_batching`) measure.

    PYTHONPATH=src python examples/serve_lm.py
"""

import sys

from repro.launch.serve import main as serve_main

if __name__ == "__main__":
    serve_main(["--arch", "qwen2.5-3b", "--smoke", "--requests", "12",
                "--max-new", "16", "--max-batch", "4", "--max-seq", "192",
                "--shared-prefix", "32"])
    serve_main(["--null-model", "--kv", "vec", "--max-batch", "128",
                "--requests", "256", "--max-new", "16",
                "--shared-prefix", "64"])
    sys.exit(0)
