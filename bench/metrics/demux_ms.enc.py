"""demux_ms.enc: mean materialize -> demux per encrypt job of the window
(ms), from the service's span stamps: the completion thread's host
finish of a job once its device output is ready, the per-row device
slices of both ciphertext polynomials. A program that stamps ``demux``
at the materialize time (no stamp after the finish) gives no reading."""


def read(r):
    jobs = {(s.t("materialize"), s.t("demux")) for s in r.window.spans
            if s.kind == "enc" and s.t("materialize") is not None
            and s.t("demux") is not None}
    if not jobs or all(b == a for a, b in jobs):
        return None
    return 1e3 * sum(b - a for a, b in jobs) / len(jobs)
