"""Share of the window spent inside the page cache's ``refresh_tables``
(harness spans, traced runs only)."""

from chipbench.metrics import span_seconds


def read(record):
    s = span_seconds(record, "refresh_tables")
    return 100.0 * sum(s) / record.window_s if s else None
