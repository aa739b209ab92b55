"""dispatch_ms.enc: mean coalesce -> launch per encrypt job of the window
(ms), from the service's span stamps: the host's preparation of a job's
operands (the df32 split of the messages, the upload) before its
kernel is enqueued."""


def read(r):
    jobs = {(s.t("coalesce"), s.t("launch")) for s in r.window.spans
            if s.kind == "enc" and s.t("launch") is not None
            and s.t("coalesce") is not None}
    if not jobs:
        return None
    return 1e3 * sum(b - a for a, b in jobs) / len(jobs)
