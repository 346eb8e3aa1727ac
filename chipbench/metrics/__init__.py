"""One reader per metric, ``chipbench/metrics/<metric>.py``, each with
``read(record) -> float | None``.  A reader that finds nothing to read
returns ``None`` and the harness leaves the metric out of the line.
The helpers below are shared arithmetic over a ``harness.Record``."""

from __future__ import annotations

from typing import List, Optional

import numpy as np

__all__ = ["window_tokens", "gaps_in_window", "span_seconds",
           "self_seconds", "kernel_roofline"]


def window_tokens(record) -> int:
    return int(sum(record.in_window(t).sum() for t in record.token_times))


def gaps_in_window(record) -> np.ndarray:
    """Every gap between consecutive tokens of a request whose later
    token falls in the window."""
    gaps: List[np.ndarray] = []
    for t in record.token_times:
        if len(t) > 1:
            gaps.append((t[1:] - t[:-1])[record.in_window(t[1:])])
    return np.concatenate(gaps) if gaps else np.zeros(0)


def span_seconds(record, name: str) -> List[float]:
    return [t1 - t0 for n, t0, t1 in record.spans if n == name]


def self_seconds(record, name: str, child: str) -> List[float]:
    """Duration of each ``name`` span less the ``child`` spans inside it."""
    children = [(t0, t1) for n, t0, t1 in record.spans if n == child]
    out = []
    for n, t0, t1 in record.spans:
        if n != name:
            continue
        inner = sum(min(c1, t1) - max(c0, t0) for c0, c1 in children
                    if c0 < t1 and c1 > t0)
        out.append(t1 - t0 - inner)
    return out


def kernel_roofline(record, kernel: str) -> Optional[float]:
    """Least time the kernel's logical bytes take at the HBM bandwidth,
    over the kernel's device time in the trace, in percent.  v5e
    publishes no int32 vector peak, so only the memory roof applies."""
    from chipbench import work

    if record.trace is None or record.peaks is None:
        return None
    seconds = record.trace["kernels"].get(kernel, {}).get("seconds", 0.0)
    calls = [l for l in record.launches if l[0] == kernel]
    if seconds <= 0 or not calls:
        return None
    nbytes = sum(work.of(kernel).hbm_bytes(*l[1:]) for l in calls)
    return 100.0 * nbytes / record.peaks["hbm_bytes_per_s"] / seconds
