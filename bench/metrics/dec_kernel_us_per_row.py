"""dec_kernel_us_per_row: device time of the decrypt+decode megakernel
(``_decrypt_decode_kernel`` events of the trace) per row it was
launched with in the window (us/row), padding rows included."""


def read(r):
    return r.trace.us_per_row("dec", r.window.dispatch) \
        if r.trace is not None else None
