"""The ``factorize_squarefree`` Pallas kernel's share of its HBM
roofline."""

from chipbench.metrics import kernel_roofline


def read(record):
    return kernel_roofline(record, "factorize_squarefree")
