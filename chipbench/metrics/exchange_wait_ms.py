"""Mean time the host waits for the sharded exchange's masks: the
program's ``kernels.sharded_exchange.fetch`` span (the mesh finishing
the all-gather and the per-shard scans, then the copy to the host), on
the host clock, traced runs only."""

import numpy as np

from chipbench.metrics import span_seconds


def read(record):
    s = span_seconds(record, "kernels.sharded_exchange.fetch")
    return float(np.mean(s)) * 1e3 if s else None
