"""Mean decode step of the window: the serve loop's ``decode.tick`` spans,
each ending once the step's tokens reached the host."""
from readings import spans_in_window


def read(run):
    ticks = spans_in_window(run, "decode.tick")
    if not ticks:
        return None
    return sum(e - s for s, e in ticks) / len(ticks) * 1e3
