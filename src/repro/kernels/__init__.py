"""Pallas TPU kernels for the PFCS factorization hot paths.

``factorize.py``  — §4.2 divisibility scan and batched squarefree
                    trial-division factorization (digit tiles x prime
                    pool grid)
``gcd.py``        — batched gcd: int32 Euclid, and squarefree gcd of
                    any width by prime-pool reconstruction
``ops.py``        — host-facing wrappers (digit packing, range checks,
                    padding, backend dispatch)
``ref.py``        — exact host oracles the kernels are tested against

Every kernel runs in int32 lanes (DESIGN.md §3): compiled on TPU,
interpreted on CPU for the tests.
"""

from .ops import divisibility_scan, factorize_batch, gcd_batch

__all__ = ["divisibility_scan", "factorize_batch", "gcd_batch"]
