"""device_idle_share: 1 − busy / window over the traced window, where busy
is the union of a device's op intervals in the profiler trace; the mean
over the devices in the trace."""

from bench import trace


def read(run):
    if run.trace is None:
        return None
    busy = trace.device_busy(run.trace)
    if not busy:
        return None
    lo, hi = trace.window(run.trace)
    window = (hi - lo) / 1e9
    return sum(1.0 - b / window for b in busy.values()) / len(busy)
