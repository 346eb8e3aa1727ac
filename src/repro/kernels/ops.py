"""Public host-facing wrappers around the PFCS Pallas kernels.

Handles the ragged real world: converts exact Python ints (or an
already-packed digit matrix) into int32 digit lanes on the host, checks
every value's range there, pads to power-of-two tile buckets, and
decides interpret mode from the backend (compiled on TPU, interpreted
on CPU, refused anywhere else).  One representation serves every width (DESIGN.md §3):
a 62-bit narrow chunk is 11 digits, a 4096-bit wide chunk 683.

Python ints in, Python ints out — these are host-callable building
blocks used by the registry/prefetcher when batch sizes justify the
device round trip.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.composite import (LIMB_BASE, MAX_PRIME_BITS, MAX_PRIME_LIMIT,
                                  n_limbs_for_bits, pack_limbs, unpack_limbs)
from repro.obs import profile
from repro.obs.profile import kernel_scope

from .factorize import (HITS_PER_ROW, divisibility_hits_pallas,
                        factorize_hits_pallas)
from .gcd import gcd_squarefree_pallas

__all__ = ["divisibility_scan", "factorize_batch", "gcd_batch",
           "prime_lanes", "interpret_mode", "check_32bit"]


def interpret_mode() -> bool:
    """Pallas interpret mode for the ``cpu`` backend only.

    The kernels compile on ``tpu``; any other backend raises rather than
    silently interpreting a kernel on a device it was not built for.
    """
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel path for backend {backend!r}")


def check_32bit() -> None:
    """The kernels run in 32-bit lanes: refuse a call under x64, where a
    stray int64 intermediate would reach Mosaic."""
    if jax.config.jax_enable_x64:
        raise RuntimeError("the discovery kernels run in 32-bit lanes; "
                           "call them outside jax.enable_x64")


def as_digits(values) -> np.ndarray:
    """Values -> ``(N, L)`` int32 digit matrix.

    A 2-D integer array is taken as an already-packed digit matrix and
    range-checked; anything else is a sequence of non-negative ints
    packed at the widest value's digit count.
    """
    if isinstance(values, np.ndarray) and values.ndim == 2 \
            and values.dtype != object:
        if values.size and (values.min() < 0 or values.max() >= LIMB_BASE):
            raise ValueError("digit matrix entries must be in "
                             f"[0, {LIMB_BASE})")
        return values.astype(np.int32)
    vals = [int(v) for v in values]
    return pack_limbs(vals, max(1, n_limbs_for_bits(
        max((v.bit_length() for v in vals), default=1))))


def prime_lanes(primes: Sequence[int]) -> np.ndarray:
    """Primes -> int32 lanes; every value must be in
    ``[0, MAX_PRIME_LIMIT)`` (0 and 1 are padding and divide nothing)."""
    vals = [int(p) for p in primes]
    for p in vals:
        if not 0 <= p < MAX_PRIME_LIMIT:
            raise ValueError(f"prime {p} is outside the {MAX_PRIME_BITS}-"
                             f"bit kernel prime bound")
    return np.asarray(vals, dtype=np.int32)


def _bucket(n: int, mult: int) -> int:
    """``mult * 2**k >= n``: power-of-two buckets bound the number of
    distinct compiled kernel shapes as registries grow."""
    width = mult
    while width < n:
        width *= 2
    return width


def _pad_to(x: np.ndarray, mult: int, fill) -> np.ndarray:
    pad = _bucket(x.shape[0], mult) - x.shape[0]
    if pad == 0:
        return x
    return np.concatenate([x, np.full(pad, fill, dtype=x.dtype)])


def _pad_rows_one(digits: np.ndarray, mult: int) -> np.ndarray:
    """Pad to a bucketed row count with rows encoding the value 1
    (divides nothing, is divided by nothing)."""
    pad = _bucket(digits.shape[0], mult) - digits.shape[0]
    if pad == 0:
        return digits
    one = np.zeros((pad, digits.shape[1]), dtype=digits.dtype)
    one[:, 0] = 1
    return np.concatenate([digits, one])


def _scan_hits(hits, mask, padded_q: int):
    """The one blocking copy of a compacting scan: ``(count of each
    padded query, registry row of each hit in (query, row) order,
    dense)``.

    ``hits`` is the launch's ``[total, counts (padded_q), rows (cap)]``
    vector.  A launch whose total overflowed its cap copies the dense
    ``(padded_q, N)`` mask as well and compacts it here (``dense``
    True), so the answer is exact either way.
    """
    hits = np.asarray(hits)
    total, per_query, rows = (int(hits[0]), hits[1:1 + padded_q],
                              hits[1 + padded_q:])
    if total <= rows.shape[0]:
        return per_query, rows[:total], False
    m = np.asarray(mask)
    return m.sum(axis=1), np.nonzero(m)[1], True


def divisibility_scan(
    registry,                       # ints, or an (N, L) digit matrix
    query_primes: Sequence[int],
    block_n: int = 256,
    block_p: int = 512,
    interpret: bool | None = None,
) -> List[np.ndarray]:
    """For each query prime, indices of registry composites it divides,
    ascending.

    The §4.2 prefetch scan.  The launch compacts its mask on the device
    (:func:`~repro.kernels.factorize.divisibility_hits_pallas`) and the
    host copies the hit rows and per-query counts, not the mask; past
    ``HITS_PER_ROW`` hits a padded query it copies the dense mask
    instead.
    """
    if interpret is None:
        interpret = interpret_mode()
    check_32bit()
    qs = prime_lanes(query_primes)
    digits = as_digits(registry)
    n, q = digits.shape[0], qs.shape[0]
    if n == 0 or q == 0:
        return [np.empty(0, dtype=np.int64) for _ in range(q)]
    padded_q = _bucket(q, block_p)
    with kernel_scope("divisibility_scan", items=n, n=n, q=q,
                      padded_n=_bucket(n, block_n), padded_q=padded_q):
        hits, mask = divisibility_hits_pallas(
            jnp.asarray(_pad_rows_one(digits, block_n)),
            jnp.asarray(_pad_to(qs, block_p, 0)),
            block_n=block_n, block_p=block_p, interpret=interpret)
        # the blocking copy: the device finishing the launch, then D2H
        with profile.span("kernels.divisibility_scan.fetch"):
            per_query, rows, dense = _scan_hits(hits, mask, padded_q)
        # the mask's set cells, every one in a real row and query
        profile.count(hits=len(rows), dense=int(dense))
        return np.split(rows.astype(np.int64),
                        np.cumsum(per_query[:q - 1]))


def factorize_batch(
    composites,                     # ints, or an (N, L) digit matrix
    primes: Sequence[int],
    block_n: int = 256,
    block_p: int = 512,
    interpret: bool | None = None,
) -> Tuple[List[List[int]], List[int]]:
    """Factor each composite against the pool.

    Returns ``(factors, residuals)`` — per composite the dividing pool
    primes in pool order and the remaining cofactor as an exact Python
    int (1 when the pool fully factors a squarefree composite).  The
    launch compacts its mask on the device
    (:func:`~repro.kernels.factorize.factorize_hits_pallas`) and the host
    copies each composite's count and first ``HITS_PER_ROW`` pool
    indices, not the mask; a launch with a composite past that copies
    the dense mask instead.
    """
    if interpret is None:
        interpret = interpret_mode()
    check_32bit()
    pool = prime_lanes(primes)
    digits = as_digits(composites)
    n, p = digits.shape[0], pool.shape[0]
    if n == 0:
        return [], []
    padded_n = _bucket(n, block_n)
    with kernel_scope("factorize_batch", items=n, n=n, p=p,
                      padded_n=padded_n, padded_p=_bucket(p, block_p)):
        hits, residual, mask = factorize_hits_pallas(
            jnp.asarray(_pad_rows_one(digits, block_n)),
            jnp.asarray(_pad_to(pool, block_p, 0)),
            block_n=block_n, block_p=block_p, interpret=interpret)
        with profile.span("kernels.factorize_batch.fetch"):
            hits, residual = np.asarray(hits), np.asarray(residual)
            per_composite = hits[:n]
            dense = int(per_composite.max()) > HITS_PER_ROW
            if dense:
                m = np.asarray(mask)[:, :n].T
        if dense:
            per_composite, cols = m.sum(axis=1), np.nonzero(m)[1]
        else:
            slots = hits[padded_n:].reshape(padded_n, HITS_PER_ROW)[:n]
            cols = slots[np.arange(HITS_PER_ROW) < per_composite[:, None]]
        flat = pool[cols].tolist()
        ends = np.cumsum(per_composite).tolist()
        factors = [flat[a:b] for a, b in zip([0] + ends[:-1], ends)]
        # dividing (composite, prime) pairs, which the kernel's division
        # rounds follow
        profile.count(hits=len(cols), dense=int(dense))
        return factors, unpack_limbs(residual[:n])


def gcd_batch(
    a,                              # ints, or an (N, L) digit matrix
    b,
    pool: Sequence[int],
    block_n: int = 256,
    block_p: int = 512,
    interpret: bool | None = None,
) -> List[int]:
    """Elementwise gcd over pairs (shared-prefix composite discovery),
    exact Python ints out.

    The values may have any width but must be squarefree products of
    primes, and ``pool`` must cover the common member primes (either
    side's prime set suffices — see
    :func:`~repro.kernels.gcd.gcd_squarefree_pallas`).  The narrower side
    is widened with zero high digits.
    """
    if interpret is None:
        interpret = interpret_mode()
    check_32bit()
    aa, bb = as_digits(a), as_digits(b)
    if aa.shape[0] != bb.shape[0]:
        raise ValueError(f"length mismatch {aa.shape[0]} vs {bb.shape[0]}")
    width = max(aa.shape[1], bb.shape[1])
    aa, bb = (np.pad(x, ((0, 0), (0, width - x.shape[1]))) for x in (aa, bb))
    primes = prime_lanes(pool)
    n = aa.shape[0]
    if n == 0:
        return []
    with kernel_scope("gcd_batch", items=n, n=n, p=len(primes),
                      padded_n=_bucket(n, block_n),
                      padded_p=_bucket(len(primes), block_p)):
        g = gcd_squarefree_pallas(
            jnp.asarray(_pad_rows_one(aa, block_n)),
            jnp.asarray(_pad_rows_one(bb, block_n)),
            jnp.asarray(_pad_to(primes, block_p, 0)),
            block_n=block_n, block_p=block_p, interpret=interpret)
        with profile.span("kernels.gcd_batch.fetch"):
            g = np.asarray(g)
        return unpack_limbs(g[:n])
