"""95th percentile, over requests whose first token falls in the window,
of first-token time minus the time the request was due.  Above the rate
the system sustains this is the admission queue's wait, which grows
through the window."""

import numpy as np


def read(record):
    waits = [t[0] - d for t, d in zip(record.token_times, record.due)
             if len(t) and record.in_window(t[0])]
    return float(np.percentile(waits, 95)) if waits else None
