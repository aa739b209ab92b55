"""Quickstart: the full CKKS client round-trip through the public API.

    PYTHONPATH=src python examples/quickstart.py [--profile test]

Walks the paper's Fig. 2a pipeline end to end:
  encode (SpecialIFFT + Delta-scale + RNS + NTT)
  -> encrypt (on-chip PRNG randomness, fused streaming kernel)
  -> [ship to server; server computes at high level, returns 2-limb ct]
  -> decrypt (c0 + c1*s, fused kernel)  -> decode (CRT + SpecialFFT)
and checks the recovered message against the original (Boot-precision
metric, paper Fig. 3c) — first through the eager per-ciphertext reference
API, then through the batched, fully device-resident ``FHEClient``
pipeline (df32 SpecialFFT Pallas kernels inside the jit; zero host FFT
round-trips, DESIGN.md §3).
"""

import argparse
import sys
import time

import numpy as np

sys.path.insert(0, "src")

from repro.core import (boot_precision_bits, decode, decode_coeff, encode,
                        get_context, keygen)
from repro.core.encryptor import Ciphertext
from repro.kernels import ops as kops


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", default="test",
                    help="tiny (N=2^6, smoke) | test (N=2^10, CPU-fast) | "
                         "n14 | n15 | paper")
    args = ap.parse_args()

    ctx = get_context(args.profile)
    p = ctx.params
    print(f"profile={args.profile}: N=2^{p.logn}, {p.n_limbs} limbs, "
          f"Delta=2^{p.delta_bits}, "
          f"logQ={ctx.modulus_bits():.0f} bits")

    sk, pk = keygen(ctx)
    rng = np.random.default_rng(0)
    z = (rng.standard_normal(p.n_slots)
         + 1j * rng.standard_normal(p.n_slots)) * 0.5

    t0 = time.perf_counter()
    pt = encode(z, ctx)
    t_encode = time.perf_counter() - t0

    t0 = time.perf_counter()
    c0, c1 = kops.encrypt_fused(pt.data, pk.b_mont, pk.a_mont, ctx)
    t_encrypt = time.perf_counter() - t0
    ct = Ciphertext(c0=c0, c1=c1, n_limbs=p.n_limbs, scale=pt.scale)

    # --- server boundary: homomorphic eval happens here (other papers');
    # the server returns a 2-limb ciphertext (paper §V-B traffic model) ----
    ct2 = Ciphertext(c0=ct.c0[:2], c1=ct.c1[:2], n_limbs=2, scale=ct.scale)

    t0 = time.perf_counter()
    m_coeff = kops.decrypt_fused(ct2.c0, ct2.c1, sk.s_mont, ctx)
    z_got = decode_coeff(m_coeff, ctx, scale=ct2.scale)
    t_decrypt = time.perf_counter() - t0

    prec = boot_precision_bits(z, z_got)
    print(f"encode   {t_encode * 1e3:8.1f} ms")
    print(f"encrypt  {t_encrypt * 1e3:8.1f} ms  (fused kernel, "
          f"{p.n_limbs} limbs, on-chip PRNG)")
    print(f"decrypt+decode {t_decrypt * 1e3:8.1f} ms  (2-limb)")
    print(f"message precision: {prec:.1f} bits "
          f"(paper requires >= 19.29)")
    assert prec >= 19.29, "round-trip precision below bootstrapping bar"

    # --- batched device-resident pipeline (FHEClient, fourier='device'):
    # df32 SpecialIFFT/FFT Pallas kernels inside the jitted cores — one
    # jitted program per direction, no host FFT round-trip ------------------
    from repro.fhe_client.client import FHEClient

    client = FHEClient(profile=args.profile)
    msgs = (rng.standard_normal((4, p.n_slots))
            + 1j * rng.standard_normal((4, p.n_slots))) * 0.5
    t0 = time.perf_counter()
    cts = client.encode_encrypt_batch(msgs)
    z_batch = client.decrypt_decode_batch(cts.truncated(2))
    t_batch = time.perf_counter() - t0
    prec_b = boot_precision_bits(msgs, z_batch)
    print(f"batched device-Fourier round-trip (B=4) {t_batch * 1e3:8.1f} ms"
          f"  precision: {prec_b:.1f} bits")
    assert prec_b >= 19.29, "device-Fourier precision below bootstrapping bar"
    print("OK — client round-trip verified")


if __name__ == "__main__":
    main()
