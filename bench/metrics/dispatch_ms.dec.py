"""dispatch_ms.dec: mean coalesce -> launch per decrypt job of the window
(ms), from the service's span stamps: from the job's ciphertexts
stacked to its kernel enqueued, the operands' placement on the device
and the launch."""


def read(r):
    jobs = {(s.t("coalesce"), s.t("launch")) for s in r.window.spans
            if s.kind == "dec" and s.t("launch") is not None
            and s.t("coalesce") is not None}
    if not jobs:
        return None
    return 1e3 * sum(b - a for a, b in jobs) / len(jobs)
