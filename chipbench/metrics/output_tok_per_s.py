"""Output tokens stamped inside the window over the window's seconds."""

from chipbench.metrics import window_tokens


def read(record):
    n = window_tokens(record)
    return n / record.window_s if n else None
