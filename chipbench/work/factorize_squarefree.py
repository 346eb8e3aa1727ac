"""``factorize_squarefree``: the divisibility pass over the pool; the
short divisions by the few dividing primes are not counted."""

from chipbench.work import HORNER_OPS, WORD


def ops(rows: int, digits: int, primes: int) -> int:
    return HORNER_OPS * rows * digits * primes


def hbm_bytes(rows: int, digits: int, primes: int) -> int:
    """Read digits and pool, write the mask and the residual digits."""
    return 2 * WORD * rows * digits + WORD * primes + rows * primes
