"""Device idle share of the traced window (%): 1 - (union of the
intervals in which an operation ran on the device) / window, from the
profiler's trace."""


def read(r):
    if r.trace is None or r.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
