"""p95_ms: 95th percentile, over every request due in the window, of the
time from when it was due to when its result was in host memory (ms,
host clock). A request whose result never came counts as the longest
wait the run allows: the window plus the minute it waits past the
close."""

import numpy as np


def read(r):
    cap = r.seconds + 60.0
    lat = [d.t_done - d.t_due if d.t_done is not None else cap
           for d in r.window.requests]
    return float(np.percentile(np.minimum(lat, cap), 95) * 1e3)
