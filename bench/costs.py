"""HBM bytes per call of the client megakernels, from shapes.

What the algorithm has to move between HBM and the core for one call,
and no more: the operands it reads once and the results it writes. The
kernel's own re-reads (the public-key limb that every batch block reads
again, the FFT twiddle table) are the implementation's choice, so they
are left out: a kernel that re-reads less comes closer to this bound.

- the encode+encrypt megakernel (``_encode_encrypt_kernel``) reads the
  four f32 df planes of the batch's slot values, (B, N/2) each, and both
  public-key polynomials, (L, N) uint32 each, and writes c0 and c1,
  (B, L, N) uint32 each.
- the decrypt+decode megakernel (``_decrypt_decode_kernel``) reads c0
  and c1 of two limbs, (B, 2, N) uint32 each, two limbs of the secret
  key, (2, N) uint32, and one f32 scale per row, and writes four f32 df
  planes, (B, N/2) each.

Neither kernel has a published compute peak to hold it against (the
VPU's 32-bit integer rate is not published for v5e), so the roofline of
these kernels is the bytes bound alone.
"""

from __future__ import annotations

import json
import os

WORD = 4                                  # uint32 / float32 bytes


def encrypt_bytes(batch: int, n: int, limbs: int) -> int:
    planes_in = 4 * batch * (n // 2) * WORD
    public_key = 2 * limbs * n * WORD
    ciphertext_out = 2 * batch * limbs * n * WORD
    return planes_in + public_key + ciphertext_out


def decrypt_bytes(batch: int, n: int) -> int:
    ciphertext_in = 2 * batch * 2 * n * WORD
    secret_key = 2 * n * WORD
    scales = batch * WORD
    planes_out = 4 * batch * (n // 2) * WORD
    return ciphertext_in + secret_key + scales + planes_out


# the megakernel as a device trace shows it -> the kind it serves. No
# pallas_call is named, so the trace names each kernel's op after the
# jitted core that holds it (``%_encrypt_core_mega32_impl.1 = ...
# custom-call(...), custom_call_target="tpu_custom_call"``); the kernel
# functions ``_encode_encrypt_kernel`` / ``_decrypt_decode_kernel`` do
# not appear in it.
KERNELS = {
    "_encrypt_core_mega32_impl": "enc",
    "_decrypt_core_mega32_impl": "dec",
}


def call_bytes(kind: str, batch: int, n: int, limbs: int) -> int:
    if kind == "enc":
        return encrypt_bytes(batch, n, limbs)
    if kind == "dec":
        return decrypt_bytes(batch, n)
    raise ValueError(f"unknown kernel kind {kind!r}")


def peaks(device_kind: str) -> dict:
    """The chip's published peaks (``peaks.json``), by JAX's
    ``device_kind``. A device that is not in the table is an error."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}")
    return table[device_kind]
