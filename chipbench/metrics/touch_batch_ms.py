"""Host self time per call of the page cache's ``touch_batch``, less the
time inside ``refresh_tables`` (harness spans, traced runs only)."""

import numpy as np

from chipbench.metrics import self_seconds


def read(record):
    s = self_seconds(record, "touch_batch", "refresh_tables")
    return float(np.mean(s)) * 1e3 if s else None
