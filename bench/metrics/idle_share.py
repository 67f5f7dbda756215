"""Share of the traced window in which no operation ran on the device."""
import devtrace


def read(run):
    if run.trace is None or not run.trace.device:
        return None
    w0, w1 = run.trace_window
    busy = [devtrace.busy_seconds([e for e in ops if w0 <= e.start <= w1])
            for ops in run.trace.device.values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (w1 - w0))
