"""Pallas TPU kernels: batched elementwise gcd.

Used by the serving tier for deterministic shared-prefix discovery and by
the sharded exchange: ``gcd(chain_composite_a, chain_composite_b)`` is
the composite of the shared pages (PFCS relationship intersection —
exact, zero false positives by unique factorization).

One kernel, in int32 lanes at any width (DESIGN.md §3).  Multi-digit
Euclid needs long division with normalization — hostile to the VPU.
PFCS composites let us sidestep it: chunk values are SQUAREFREE products
of pool primes, so

    gcd(a, b) = prod { p in pool : p | a  and  p | b }

exactly (unique factorization — Theorem 1).  The kernel computes both
divisibility masks with the Horner ladder of ``factorize.py`` and
rebuilds the gcd by masked schoolbook multiplication into a digit
accumulator, least significant digit first:

    t = digit * p + carry      digit < 2**6, carry < p < 2**25
                               => t < p * 2**6 <= 2**31          OK

The caller supplies a prime pool covering the common factors (any
superset of either side's member primes works — common primes are a
subset of both).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.composite import LIMB_BITS, LIMB_MASK

from .factorize import _check, _divides, _specs

__all__ = ["gcd_squarefree_pallas"]


def _gcd_squarefree_kernel(a_ref, b_ref, p_ref, ps_ref, g_ref, take_ref):
    j = pl.program_id(1)
    L = a_ref.shape[0]
    p = p_ref[...]
    common = jnp.logical_and(_divides(a_ref, p), _divides(b_ref, p))
    take_ref[...] = common.astype(jnp.int32)

    # accumulator: the value 1 on the first prime tile
    @pl.when(j == 0)
    def _init():
        g_ref[...] = (lax.broadcasted_iota(jnp.int32, g_ref.shape, 0) == 0
                      ).astype(jnp.int32)

    def per_prime(jj, _):
        pj = jnp.maximum(ps_ref[0, jj], 1)
        take = take_ref[pl.ds(jj, 1), :] != 0             # (1, BN)

        def step(k, carry):
            row = pl.ds(k, 1)
            t = g_ref[row, :] * pj + carry
            g_ref[row, :] = jnp.where(take, t & LIMB_MASK, g_ref[row, :])
            return t >> LIMB_BITS

        lax.fori_loop(0, L, step, jnp.zeros(take.shape, jnp.int32))
        return 0

    lax.fori_loop(0, ps_ref.shape[1], per_prime, 0)


@functools.partial(jax.jit, static_argnames=("block_n", "block_p", "interpret"))
def gcd_squarefree_pallas(
    a: jnp.ndarray,            # (N, L) int32 digits, N % block_n == 0
    b: jnp.ndarray,            # (N, L) same
    pool: jnp.ndarray,         # (P,) int32 primes covering common factors
    *,
    block_n: int = 256,
    block_p: int = 512,
    interpret: bool = False,
):
    """Elementwise gcd of squarefree composite pairs, as ``(N, L)`` digits.

    Exact for values that are products of distinct ``pool`` primes (the
    registry invariant).  Pad rows of value 1 and zero-padded pool
    primes yield gcd 1 — callers slice to the live prefix.
    """
    assert a.shape == b.shape, (a.shape, b.shape)
    n, p = _check(a, pool, block_n, block_p)
    _check(b, pool, block_n, block_p)
    L = a.shape[1]
    dspec, pspec, sspec, _ = _specs(L, block_n, block_p)
    g = pl.pallas_call(
        _gcd_squarefree_kernel,
        grid=(n // block_n, p // block_p),
        in_specs=[dspec, dspec, pspec, sspec],
        out_specs=dspec,
        out_shape=jax.ShapeDtypeStruct((L, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_p, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="gcd_squarefree",
    )(a.T, b.T, pool.reshape(p, 1), pool.reshape(p // block_p, 1, block_p))
    return g.T
