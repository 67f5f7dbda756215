"""Mean time the decode worker waited for the serving loop's lock before
each decode step of the window: the program's ``decode.wait`` span that
ends between the previous step and this one (0 where none, as after an
idle spell).  Nothing where the program records no ``decode.wait``."""
import bisect

from readings import spans_in_window


def read(run):
    waits = sorted((e, s) for n, s, e, _ in run.spans if n == "decode.wait")
    ticks = spans_in_window(run, "decode.tick")
    if not waits or not ticks:
        return None
    every = sorted((s, e) for n, s, e, _ in run.spans if n == "decode.tick")
    ends = [e for e, _ in waits]
    total = 0.0
    for s, _ in ticks:
        i = bisect.bisect_left(every, (s, float("-inf")))
        after = every[i - 1][1] if i else float("-inf")
        j = bisect.bisect_right(ends, s) - 1
        if j >= 0 and ends[j] >= after:
            total += ends[j] - waits[j][1]
    return total / len(ticks) * 1e3
