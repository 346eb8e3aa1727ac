"""Device time of the exchange's all-gather, per device and per exchange
launch: every ``all-gather`` op of the trace (the reduction averages op
time over the device planes) over the program's
``kernels.sharded_exchange`` spans in the window."""

from chipbench.metrics import span_seconds


def read(record):
    tr = record.trace
    if tr is None:
        return None
    seconds = sum(v for k, v in tr["op_seconds"].items()
                  if k.startswith("all-gather"))
    launches = len(span_seconds(record, "kernels.sharded_exchange"))
    return 1e3 * seconds / launches if seconds > 0 and launches else None
