"""Exact oracles for the PFCS Pallas kernels.

These are the semantic ground truth the kernels are validated against
(tests sweep shapes and widths and assert exact equality — integer
kernels, no tolerance needed).  The digit-matrix oracles are
arbitrary-precision Python-int arithmetic: unpack digits -> exact int
ops -> repack.  Deliberately NOT the kernels' digit ladder — there is
nothing to get subtly wrong here, which is the point of an oracle.
"""

from __future__ import annotations

import math

import numpy as np

from repro.core.composite import pack_limbs, unpack_limbs

__all__ = ["divisibility_mask_ref", "factorize_squarefree_ref",
           "gcd_squarefree_ref"]


def divisibility_mask_ref(digits: np.ndarray, primes) -> np.ndarray:
    """mask[i, j] = primes[j] divides the composite encoded by digits[i].

    digits: (N, L) digit matrix -> (N, P) bool; primes <= 1 (padding)
    never divide.
    """
    vals = unpack_limbs(digits)
    ps = [int(p) for p in np.asarray(primes)]
    return np.array([[p > 1 and v % p == 0 for p in ps] for v in vals],
                    dtype=bool).reshape(len(vals), len(ps))


def factorize_squarefree_ref(digits: np.ndarray, primes):
    """Squarefree factorization against a prime pool.

    PFCS composites are products of *distinct* primes (one per data
    element), so the divisibility mask IS the factorization.  Returns
    ``(mask, residual_digits)`` where the residual is ``c // prod of
    dividing pool primes`` — 1 when the pool fully factors the
    composite, else the cofactor for the next (colder) pool / Pollard
    stage — repacked at the input digit width.
    """
    vals = unpack_limbs(digits)
    mask = divisibility_mask_ref(digits, primes)
    ps = [int(p) for p in np.asarray(primes)]
    residuals = []
    for i, v in enumerate(vals):
        prod = math.prod(p for j, p in enumerate(ps) if mask[i, j])
        residuals.append(v // prod)
    return mask, pack_limbs(residuals, np.asarray(digits).shape[1])


def gcd_squarefree_ref(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Elementwise exact gcd of digit-encoded pairs, repacked digits.

    This is FULL math.gcd — it equals the kernel's pool-reconstruction
    gcd exactly when both sides are squarefree products of pool primes
    (the registry invariant the differential fuzz pins).
    """
    va, vb = unpack_limbs(a), unpack_limbs(b)
    return pack_limbs([math.gcd(x, y) for x, y in zip(va, vb)],
                      np.asarray(a).shape[1])
