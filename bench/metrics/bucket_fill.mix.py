"""bucket_fill.mix: real rows over launched rows of the window's jobs
(%), from the service's dispatch log: the share of the kernels' rows
that carried a request rather than padding."""


def read(r):
    launched = sum(d.bucket for d in r.window.dispatch)
    if not launched:
        return None
    return 100.0 * sum(len(d.rids) for d in r.window.dispatch) / launched
