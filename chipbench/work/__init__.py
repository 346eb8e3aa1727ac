"""Logical operations and bytes of each kernel, one file per kernel:
``chipbench/work/<kernel>.py`` with ``ops(*shape)`` and
``hbm_bytes(*shape)``, where ``shape`` is what a driver logs for each
launch of that kernel.  Counts come from the problem as the
``repro.kernels.ops`` wrappers receive it (rows, digits per row, query
or pool primes), never from the padded launch shape, so a change of
padding or tiling moves the kernel's time and not its count."""

from __future__ import annotations

import importlib

__all__ = ["of", "WORD", "HORNER_OPS"]

#: digits and primes are int32
WORD = 4
#: one Horner step of the divisibility ladder: multiply, add, remainder
HORNER_OPS = 3


def of(kernel: str):
    """The work module of a kernel, by its name in the trace."""
    return importlib.import_module(f"chipbench.work.{kernel}")
