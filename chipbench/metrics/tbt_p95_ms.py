"""95th percentile of every gap between consecutive output tokens of a
request, over gaps that end in the window; each token is stamped at the
end of the step that produced it."""

import numpy as np

from chipbench.metrics import gaps_in_window


def read(record):
    gaps = gaps_in_window(record)
    return float(np.percentile(gaps, 95)) * 1e3 if gaps.size else None
