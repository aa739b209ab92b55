"""demux_ms.dec: mean materialize -> demux per decrypt job of the window
(ms), from the service's span stamps: the completion thread's host
finish of a job once its device output is ready, the readback of the
four f32 planes and the f64 collapse in ``decrypt_results``, then the
per-row split. A program that stamps ``demux`` at the materialize time
(no stamp after the finish) gives no reading."""


def read(r):
    jobs = {(s.t("materialize"), s.t("demux")) for s in r.window.spans
            if s.kind == "dec" and s.t("materialize") is not None
            and s.t("demux") is not None}
    if not jobs or all(b == a for a, b in jobs):
        return None
    return 1e3 * sum(b - a for a, b in jobs) / len(jobs)
