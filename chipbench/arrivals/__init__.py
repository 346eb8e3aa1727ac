"""Arrival processes, one file each: ``chipbench/arrivals/<name>.py``
with ``due_s(k, params) -> float``, the seconds after the window opens
at which the ``k``-th request after a mix's fill is due.  ``params`` is
the mix's ``arrival`` object; ``k`` counts from 0 and ``due_s`` does not
decrease with it."""
