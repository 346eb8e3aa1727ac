"""Pallas TPU kernels: §4.2 divisibility scan and batched squarefree
factorization by trial division.

PFCS Algorithm 2 stage 1 (trial division against a prime pool), adapted
from the paper's per-access scalar loop to a TPU-native *batched* kernel:
the registry refresh / bulk relationship-discovery path factorizes many
composites against a whole pool at once.

Digits in int32 lanes (DESIGN.md §3).  A composite of any width is its
little-endian ``LIMB_BITS``-bit digits (``core.composite.pack_limbs``),
and the kernels take them digit-major, ``(L, N)``: composites along
lanes, digit ``k`` of a whole tile one sublane row.  Primes are below
``MAX_PRIME_LIMIT`` (2**25), so with 6-bit digits every step is exact
int32 arithmetic:

    Horner remainder   r = (r * 2**6 + digit) % p      r < p
                       => r * 2**6 + digit < p * 2**6 <= 2**31
    short division     cur = carry * 2**6 + digit;  q = cur // d
                       d <= p, carry < d  => the same bound; q < 2**6

Layout of one (BP primes) x (BN composites) grid cell:
    digits tile      (L, BN)  int32  VMEM
    primes tile      (BP, 1)  int32  VMEM — broadcast along lanes
    mask out tile    (BP, BN) bool   — mask[j, i] = p_j | c_i
    residual out     (L, BN)  int32  — c_i // prod(dividing p_j)
    take scratch     (BP, BN) int32  — the mask, read by every round

Grid: (N/BN, P/BP).  The prime axis (j) is the innermost, sequentially
executed grid dimension, so the residual tile accumulates the running
cofactor across prime tiles: the composite at j == 0, then divided by
every dividing prime as tiles stream through (the matmul K-loop
accumulator pattern).

Inside a tile the division runs in rounds that follow the hits, not the
pool.  Each round divides every lane by a divisor ``d`` of its own, a
``(1, BN)`` row: the prime of the lane's highest dividing mask row below
the one it took last (a masked max of a row-index iota over sublanes,
then a masked max of the primes), or 1 once the lane has none left,
which leaves its digits as they are.  Rows are picked by index, so a
prime repeated in the pool divides once per row, as the oracle does.
The tile runs as many rounds as its most-divided lane has dividing
primes, a count reduced from the mask: none for a tile nothing divides,
one or two for the two-prime composites of chain edges, never more than
BP — where the walk over every prime ran BP.  No dynamic lane indexing.

Two entries run each kernel: ``divisibility_mask_pallas`` and
``factorize_squarefree_pallas`` return the dense mask, and
``divisibility_hits_pallas`` and ``factorize_hits_pallas`` compact it in
the same jitted program into a fixed-size vector of hit coordinates
(``_compact_rows``, ``_first_rows``), which is all the host wrappers
copy.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.composite import LIMB_BITS

__all__ = ["divisibility_mask_pallas", "divisibility_hits_pallas",
           "factorize_squarefree_pallas", "factorize_hits_pallas",
           "HITS_PER_ROW"]

_BASE = 1 << LIMB_BITS
#: lanes of one compaction block (:func:`_compact_rows`)
_BLOCK = 128
#: hit slots a compacting launch keeps: per padded query prime of a
#: scan, per padded composite of a factorization.  A launch with more
#: leaves its wrapper the dense mask to copy.  A chain edge is divided
#: by two primes, so the page cache's launches fill about half.
HITS_PER_ROW = 4


def _divides(c_ref, p):
    """``(BP, BN)`` bool: prime row ``j`` divides composite column ``i``.

    ``c_ref`` holds ``(L, BN)`` digits, ``p`` the ``(BP, 1)`` primes.
    Primes <= 1 (padding) divide nothing.  Horner's rule runs most
    significant digit first, one dynamic sublane row per step.
    """
    L, bn = c_ref.shape
    safe_p = jnp.maximum(p, 1)

    def step(k, r):
        return (r * _BASE + c_ref[pl.ds(L - 1 - k, 1), :]) % safe_p

    r = lax.fori_loop(0, L, step, jnp.zeros((p.shape[0], bn), jnp.int32))
    return jnp.logical_and(r == 0, p > 1)


def _divmask_kernel(c_ref, p_ref, mask_ref):
    mask_ref[...] = _divides(c_ref, p_ref[...])


def _factorize_kernel(c_ref, p_ref, mask_ref, res_ref, take_ref):
    j = pl.program_id(1)
    L = c_ref.shape[0]
    divides = _divides(c_ref, p_ref[...])
    mask_ref[...] = divides
    take_ref[...] = divides.astype(jnp.int32)

    @pl.when(j == 0)
    def _init():
        res_ref[...] = c_ref[...]

    # divide the residual by every dividing prime of this tile, one per
    # lane a round: floor(floor(c / a) / b) == floor(c / (a * b)), so the
    # result equals c // prod(dividing primes) exactly.  A round takes, in
    # each lane, the highest dividing row below the one it took last; the
    # tile runs as many rounds as its most-divided lane has hits.
    rows = lax.broadcasted_iota(jnp.int32, take_ref.shape, 0)
    rounds = jnp.max(jnp.sum(take_ref[...], axis=0))

    def per_round(_, last):                 # (1, BN): each lane's last row
        left = jnp.logical_and(take_ref[...] != 0, rows < last)
        row = jnp.max(jnp.where(left, rows, -1), axis=0, keepdims=True)
        # the row's prime, 1 in a lane with none left: divides by nothing
        d = jnp.max(jnp.where(rows == row, p_ref[...], 1), axis=0,
                    keepdims=True)

        def step(k, carry):
            at = pl.ds(L - 1 - k, 1)
            cur = carry * _BASE + res_ref[at, :]
            q = cur // d
            res_ref[at, :] = q
            return cur - q * d

        lax.fori_loop(0, L, step, jnp.zeros(row.shape, jnp.int32))
        return row

    lax.fori_loop(0, rounds, per_round,
                  jnp.full((1, take_ref.shape[1]), take_ref.shape[0],
                           jnp.int32))


def _specs(L: int, block_n: int, block_p: int):
    digits = pl.BlockSpec((L, block_n), lambda i, j: (0, i))
    primes = pl.BlockSpec((block_p, 1), lambda i, j: (j, 0))
    scalars = pl.BlockSpec((None, 1, block_p), lambda i, j: (j, 0, 0),
                           memory_space=pltpu.SMEM)
    mask = pl.BlockSpec((block_p, block_n), lambda i, j: (j, i))
    return digits, primes, scalars, mask


def _check(digits, primes, block_n: int, block_p: int):
    n, _ = digits.shape
    p = primes.shape[0]
    assert digits.dtype == jnp.int32 and primes.dtype == jnp.int32, \
        (digits.dtype, primes.dtype)
    assert n % block_n == 0 and p % block_p == 0, (n, p, block_n, block_p)
    return n, p


def _divmask_call(digits, primes, block_n: int, block_p: int,
                  interpret: bool):
    """The scan's ``pallas_call``: the ``(P, N)`` query-major mask."""
    n, p = _check(digits, primes, block_n, block_p)
    L = digits.shape[1]
    dspec, pspec, _, mspec = _specs(L, block_n, block_p)
    return pl.pallas_call(
        _divmask_kernel,
        grid=(n // block_n, p // block_p),
        in_specs=[dspec, pspec],
        out_specs=mspec,
        out_shape=jax.ShapeDtypeStruct((p, n), jnp.bool_),
        interpret=interpret,
        name="divisibility_mask",
    )(digits.T, primes.reshape(p, 1))


def _factorize_call(digits, primes, block_n: int, block_p: int,
                    interpret: bool):
    """The factorization's ``pallas_call``: the ``(P, N)`` prime-major
    mask and the ``(L, N)`` digit-major residual."""
    n, p = _check(digits, primes, block_n, block_p)
    L = digits.shape[1]
    dspec, pspec, _, mspec = _specs(L, block_n, block_p)
    return pl.pallas_call(
        _factorize_kernel,
        grid=(n // block_n, p // block_p),
        in_specs=[dspec, pspec],
        out_specs=[mspec, dspec],
        out_shape=[jax.ShapeDtypeStruct((p, n), jnp.bool_),
                   jax.ShapeDtypeStruct((L, n), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((block_p, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="factorize_squarefree",
    )(digits.T, primes.reshape(p, 1))


def _prefix_counts(x):
    """Inclusive running sums along the last axis of a small-count
    ``(K, W)`` array, as one matmul by an upper-triangular matrix of
    ones: exact in float32 while every sum stays below 2**24."""
    w = x.shape[-1]
    upper = jnp.triu(jnp.ones((w, w), jnp.float32))
    return jnp.dot(x.astype(jnp.float32), upper,
                   precision=lax.Precision.HIGHEST).astype(jnp.int32)


def _compact_rows(mask, cap: int):
    """The set cells of a ``(R, C)`` bool mask in row-major order, found
    on the device: ``(total, per_row, cols)``, where ``per_row`` is the
    ``(R,)`` count of each row and ``cols`` the column of each of the
    first ``cap`` set cells, -1 past ``total``.

    Hit ``k`` lies in the row where the running count passes ``k``;
    in that row, in the block of ``_BLOCK`` lanes where the row's
    running count passes the hit's rank in the row; in that block, at
    the lane where the block's own running count passes its rank in the
    block.  Each step
    counts, for all ``cap`` hits at once, the rows, blocks or lanes
    wholly before it: compares and sums, two gathers of whole rows, no
    search loop, no scatter, and nothing sized by the data.
    """
    r, c = mask.shape
    width = math.gcd(c, _BLOCK)
    blocks = mask.reshape(r * (c // width), width)
    per_block = jnp.sum(blocks, axis=1, dtype=jnp.int32).reshape(r, -1)
    per_row = jnp.sum(per_block, axis=1)
    row_end = jnp.cumsum(per_row)
    total = row_end[-1]
    k = jnp.arange(cap, dtype=jnp.int32)

    def locate(ends, counts, rank):
        """(index, rank inside it) of each hit's segment, ``ends`` the
        segments' running counts, broadcast against ``rank[:, None]``."""
        before = ends <= rank[:, None]
        return (jnp.sum(before, axis=1, dtype=jnp.int32),
                rank - jnp.sum(jnp.where(before, counts, 0), axis=1))

    row, rank = locate(row_end[None, :], per_row[None, :], k)
    row = jnp.minimum(row, r - 1)
    counts = per_block[row]                               # (cap, C / width)
    block, rank = locate(_prefix_counts(counts), counts, rank)
    block = jnp.minimum(block, counts.shape[1] - 1)
    lanes = blocks[row * counts.shape[1] + block]         # (cap, width)
    lane = jnp.sum(_prefix_counts(lanes) <= rank[:, None], axis=1)
    return total, per_row, jnp.where(k < total, block * width + lane, -1)


def _first_rows(mask, slots: int):
    """For each column of a ``(R, C)`` bool mask, its set count and the
    rows of its first ``slots`` set cells, ascending, ``R`` past the
    count: ``(per_col (C,), rows (C, slots))``.  One pass over the mask
    a slot, each a masked minimum down the columns."""
    r = mask.shape[0]
    index = lax.broadcasted_iota(jnp.int32, mask.shape, 0)
    last = jnp.full((mask.shape[1],), -1, jnp.int32)
    rows = []
    for _ in range(slots):
        last = jnp.min(jnp.where(mask & (index > last), index, r), axis=0)
        rows.append(last)
    return jnp.sum(mask, axis=0, dtype=jnp.int32), jnp.stack(rows, axis=1)


@functools.partial(jax.jit, static_argnames=("block_n", "block_p", "interpret"))
def divisibility_mask_pallas(
    digits: jnp.ndarray,       # (N, L) int32 digits, N % block_n == 0
    primes: jnp.ndarray,       # (P,) int32 query primes, P % block_p == 0
    *,
    block_n: int = 256,
    block_p: int = 512,
    interpret: bool = False,
):
    """Prefetch candidate scan (§4.2): ``mask[i, j] = primes[j] | c_i``,
    the dense ``(N, P)`` mask.  ``ops.divisibility_scan`` and the
    sharded exchange run the same kernel through
    :func:`divisibility_hits_pallas` instead, which compacts the mask on
    the device into ``HITS_PER_ROW`` slots a padded query and leaves the
    host the dense mask only when the hits overflow them.  Padding rows
    (value 0 or 1) match nothing; zero-padded primes never divide.
    """
    return _divmask_call(digits, primes, block_n, block_p, interpret).T


@functools.partial(jax.jit, static_argnames=("block_n", "block_p", "interpret"))
def divisibility_hits_pallas(
    digits: jnp.ndarray,       # (N, L) int32 digits, N % block_n == 0
    primes: jnp.ndarray,       # (P,) int32 query primes, P % block_p == 0
    *,
    block_n: int = 256,
    block_p: int = 512,
    interpret: bool = False,
):
    """The scan with its hits compacted on the device, in one program.

    Returns ``(hits, mask)``.  ``hits`` is one int32 vector: the number
    of set cells, the ``(P,)`` hit count of each query prime, then the
    registry row of each of the first ``HITS_PER_ROW * P`` hits in
    (query, row) order, -1 past the total.  ``mask`` is the ``(P, N)``
    query-major mask, left on the device for a host that finds the
    total above that cap.
    """
    mask = _divmask_call(digits, primes, block_n, block_p, interpret)
    total, per_query, rows = _compact_rows(mask,
                                           HITS_PER_ROW * primes.shape[0])
    return jnp.concatenate([total[None], per_query, rows]), mask


@functools.partial(jax.jit, static_argnames=("block_n", "block_p", "interpret"))
def factorize_squarefree_pallas(
    digits: jnp.ndarray,       # (N, L) int32 digits, N % block_n == 0
    primes: jnp.ndarray,       # (P,) int32 pool, P % block_p == 0
    *,
    block_n: int = 256,
    block_p: int = 512,
    interpret: bool = False,
):
    """Returns ``(mask (N, P) bool, residual (N, L) int32 digits)``:
    the dividing pool primes and ``c // prod(dividing primes)`` (1 when
    the pool fully factors a squarefree composite) — see the ref.py
    oracle."""
    mask, residual = _factorize_call(digits, primes, block_n, block_p,
                                     interpret)
    return mask.T, residual.T


@functools.partial(jax.jit, static_argnames=("block_n", "block_p", "interpret"))
def factorize_hits_pallas(
    digits: jnp.ndarray,       # (N, L) int32 digits, N % block_n == 0
    primes: jnp.ndarray,       # (P,) int32 pool, P % block_p == 0
    *,
    block_n: int = 256,
    block_p: int = 512,
    interpret: bool = False,
):
    """The factorization with its mask compacted on the device, in one
    program.

    Returns ``(hits, residual (N, L), mask (P, N))``.  ``hits`` is one
    int32 vector: the ``(N,)`` count of each composite's dividing pool
    primes, then for each composite the pool indices of its first
    ``HITS_PER_ROW`` dividing primes, ascending (P past the count).
    ``mask`` is left on the device for a host that finds a count above
    ``HITS_PER_ROW``.
    """
    mask, residual = _factorize_call(digits, primes, block_n, block_p,
                                     interpret)
    per_composite, prime_of = _first_rows(mask, HITS_PER_ROW)
    return (jnp.concatenate([per_composite, prime_of.reshape(-1)]),
            residual.T, mask)
