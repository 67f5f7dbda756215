"""Median wait of an admission that started in the window, from when the
request could first have been admitted (the queue head, with a slot
free) to the start of its admission: the program's
``request.admit_wait`` span, the part of the queue wait spent on the
serving loop's lock rather than behind earlier requests.  Nothing where
the program records no ``request.admit_wait``."""
from stats import percentile


def read(run):
    waits = [e - s for n, s, e, _ in run.spans
             if n == "request.admit_wait" and run.in_window(e)]
    p = percentile(waits, 50)
    return None if p is None else p * 1e3
