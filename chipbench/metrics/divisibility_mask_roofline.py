"""The ``divisibility_mask`` Pallas kernel's share of its HBM roofline."""

from chipbench.metrics import kernel_roofline


def read(record):
    return kernel_roofline(record, "divisibility_mask")
