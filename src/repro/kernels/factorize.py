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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.composite import LIMB_BITS

__all__ = ["divisibility_mask_pallas", "factorize_squarefree_pallas"]

_BASE = 1 << LIMB_BITS


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


@functools.partial(jax.jit, static_argnames=("block_n", "block_p", "interpret"))
def divisibility_mask_pallas(
    digits: jnp.ndarray,       # (N, L) int32 digits, N % block_n == 0
    primes: jnp.ndarray,       # (P,) int32 query primes, P % block_p == 0
    *,
    block_n: int = 256,
    block_p: int = 512,
    interpret: bool = False,
):
    """Prefetch candidate scan (§4.2): ``mask[i, j] = primes[j] | c_i``.

    The host compacts per-query candidate lists from the ``(N, P)`` mask.
    Padding rows (value 0 or 1) match nothing; zero-padded primes never
    divide.
    """
    n, p = _check(digits, primes, block_n, block_p)
    L = digits.shape[1]
    dspec, pspec, _, mspec = _specs(L, block_n, block_p)
    mask = pl.pallas_call(
        _divmask_kernel,
        grid=(n // block_n, p // block_p),
        in_specs=[dspec, pspec],
        out_specs=mspec,
        out_shape=jax.ShapeDtypeStruct((p, n), jnp.bool_),
        interpret=interpret,
        name="divisibility_mask",
    )(digits.T, primes.reshape(p, 1))
    return mask.T


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
    n, p = _check(digits, primes, block_n, block_p)
    L = digits.shape[1]
    dspec, pspec, _, mspec = _specs(L, block_n, block_p)
    mask, residual = pl.pallas_call(
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
    return mask.T, residual.T
