"""``divisibility_mask``: every query prime against every composite, one
Horner pass; a mask entry is one byte."""

from chipbench.work import HORNER_OPS, WORD


def ops(rows: int, digits: int, primes: int) -> int:
    return HORNER_OPS * rows * digits * primes


def hbm_bytes(rows: int, digits: int, primes: int) -> int:
    """Read the digit matrix and the primes once, write the mask once."""
    return WORD * rows * digits + WORD * primes + rows * primes
