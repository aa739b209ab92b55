"""queue_wait_ms.mix: 95th percentile of submit -> launch over the
window's requests (ms), from the service's span stamps: the wait in the
service front and the batcher before a request's job launches."""

import numpy as np


def read(r):
    waits = [s.t("launch") - s.t("submit") for s in r.window.spans
             if s.t("launch") is not None]
    if not waits:
        return None
    return float(np.percentile(waits, 95) * 1e3)
