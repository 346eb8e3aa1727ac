"""An open loop at a fixed rate behind a backlog: the first ``backlog``
requests are due as the window opens, the queue a system above its
capacity holds, then one request every ``1 / rate_per_s`` seconds."""


def due_s(k: int, params) -> float:
    backlog = int(params["backlog"])
    if k < backlog:
        return 0.0
    return (k - backlog + 1) / float(params["rate_per_s"])
