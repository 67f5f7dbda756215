"""Median wait of a request due in the window, from its due time to its
admission (the serve loop's queue)."""
from stats import percentile


def read(run):
    waits = [r.admitted - r.due for r in run.reqs.values()
             if run.in_window(r.due) and r.admitted is not None]
    p = percentile(waits, 50)
    return None if p is None else p * 1e3
