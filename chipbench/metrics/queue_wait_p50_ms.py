"""Median wait from a request's due time to its admission (the engine's
own `t_admitted`), over requests admitted in the traced window."""
from record import percentile


def read(run):
    waits = [(r.admitted - r.due) * 1e3 for r in run.requests
             if r.admitted is not None and run.in_traced(r.admitted)
             and run.in_window(r.due)]
    return percentile(waits, 50)
