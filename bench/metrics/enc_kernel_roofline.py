"""enc_kernel_roofline: the encode+encrypt megakernel's share of its
roofline (%): the bytes it must move (``costs.encrypt_bytes`` per call,
from the launched bucket) over the chip's HBM bandwidth, divided by its
device time in the trace. Bytes-bound: no compute peak is published for
the VPU's 32-bit integer work."""


def read(r):
    if r.trace is None:
        return None
    return r.trace.roofline("enc", r.window.dispatch, r.n, r.limbs,
                            r.peaks["hbm_bytes_per_s"])
