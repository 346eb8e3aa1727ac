"""Mean time of a successor-table rebuild on the four-chip mesh: the
program's ``pages.refresh`` span, on the host clock (traced runs, with
the program's spans recorded into the harness's)."""

import numpy as np

from chipbench.metrics import span_seconds


def read(record):
    s = span_seconds(record, "pages.refresh")
    return float(np.mean(s)) * 1e3 if s else None
