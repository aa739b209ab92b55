"""enc_kernel_us_per_row: device time of the encode+encrypt megakernel
(``_encode_encrypt_kernel`` events of the trace) per row it was
launched with in the window (us/row), padding rows included."""


def read(r):
    return r.trace.us_per_row("enc", r.window.dispatch) \
        if r.trace is not None else None
