"""Double-float32 SpecialFFT/SpecialIFFT Pallas kernel (paper Fig. 3c).

The ASIC's reconfigurable Fourier engine runs the canonical-embedding FFT in
a custom FP55 (43 mantissa bits). The TPU datapath is double-float32 — an
unevaluated (hi, lo) fp32 pair with ~49 effective mantissa bits, built from
native VPU f32 ops only (Dekker TwoProd, no FMA assumed). 49 >= 43 keeps the
bootstrapping precision above the paper's 19.29-bit requirement.

Layout: a complex df32 array is four f32 planes (re_hi, re_lo, im_hi, im_lo).
Inside the kernel each (rows, n) plane is viewed tiled as (rows, R, C)
(``common.tile_layout``) and every butterfly partner is a lane or row roll
away. Stage twiddles are *tables*: the 5^j rot-group orbit makes the FFT
twiddle sequence non-geometric, so unlike the NTT the doubling OTF
generator does not apply (recorded in DESIGN.md). Each stage's twiddles
are expanded to one df32 entry per element (16 bytes x n per stage, 7.5 MB
for the 15 stages at n = 2^15) and stay VMEM-resident.

Bit-reversal is applied OUTSIDE the kernel (an XLA gather): the kernel
runs the stage pipeline relabeled by the bit reversal, so the permutation
lands on the kernel's input (encode) or output (decode), as the hardware
commutators do.

Two entry layers:
  * ``special_fft_planes`` / ``special_ifft_planes`` — jit-traceable, four
    (rows, n) f32 planes in/out. These nest inside the client's jitted
    encode/decrypt cores, making the whole pipeline device-resident (the
    ``ops.fourier`` FFT mode).
  * ``special_fft_rows`` / ``special_ifft_rows`` — numpy complex128
    convenience wrappers over the plane layer (tests, eager callers).
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import dfloat as dfl
from repro.core import fft as fftmod
from repro.core.ntt import bitrev_indices
from repro.kernels import common


# ---------------------------------------------------------------------------
# Host-side stage twiddle tables
# ---------------------------------------------------------------------------

_TW_MEMO: dict[tuple[int, int, bool], np.ndarray] = {}


def stage_twiddles(n: int, m: int, inverse: bool) -> np.ndarray:
    """(S, 4, R, C) f32 df planes (re_hi, re_lo, im_hi, im_lo) of every
    stage's twiddles, expanded to one entry per element of the tiled
    (R, C) slot layout (``common.tile_layout`` of the 2n-point ring), in
    the bit-reversal-relabeled order ``fft_stage_pipeline`` runs in.

    Stage s of the natural-order transform pairs i with i + lenh and
    multiplies by w_s[i mod lenh]; relabeled by the bit reversal rev,
    element p = rev(i) reads w_s[rev(p) mod lenh]. The values are the
    complex128 roots split into f32 pairs elementwise, as before."""
    key = (n, m, inverse)
    if key in _TW_MEMO:
        return _TW_MEMO[key]
    roots = fftmod.unit_roots(m)
    rev = bitrev_indices(n)
    lengths = []
    length = n if inverse else 2
    while 2 <= length <= n:
        lengths.append(length)
        length = length // 2 if inverse else length * 2
    stages = []
    for length in lengths:
        lenh = length // 2
        if inverse:
            lenq = length * 4
            rg = fftmod.rot_group(n, m)[:lenh]
            w = roots[(lenq - (rg % lenq)) * (m // lenq)]
        else:
            w = roots[fftmod._stage_indices(n, m, length)]
        w = w[rev % lenh]
        re_hi = w.real.astype(np.float32)
        re_lo = (w.real - re_hi).astype(np.float32)
        im_hi = w.imag.astype(np.float32)
        im_lo = (w.imag - im_hi).astype(np.float32)
        stages.append(np.stack([re_hi, re_lo, im_hi, im_lo]))
    cols = common.tile_layout(2 * n)[1]
    out = np.stack(stages).reshape(len(stages), 4, n // cols, cols)
    _TW_MEMO[key] = out
    return out


def bitrev_gather(planes):
    """Bit-reversal permutation of (rows, n) planes along the last axis —
    an XLA gather outside the kernels, which run relabeled instead."""
    rev = bitrev_indices(planes[0].shape[-1]).astype(np.int32)  # x64-free
    return tuple(p[..., rev] for p in planes)


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------


def _pairs(z: dfl.DFComplex, shift, axis: int):
    halves = [common.butterfly_pairs(p, shift, axis)
              for p in dfl.dfc_to_planes(z)]
    u = dfl.dfc_from_planes(tuple(h[0] for h in halves))
    v = dfl.dfc_from_planes(tuple(h[1] for h in halves))
    return u, v, halves[0][2]


def _select(mask, a: dfl.DFComplex, b: dfl.DFComplex) -> dfl.DFComplex:
    return dfl.dfc_from_planes(tuple(
        jnp.where(mask, x, y)
        for x, y in zip(dfl.dfc_to_planes(a), dfl.dfc_to_planes(b))))


def fft_stage_pipeline(x: dfl.DFComplex, tw_ref, *, n: int,
                       inverse: bool) -> dfl.DFComplex:
    """The df32 stage pipeline on tiled (rows, R, C) DFComplex planes —
    the kernel body's compute, shared by the standalone FFT kernel and
    the client streaming megakernels (``client_stream``).

    Runs relabeled by the bit reversal: the forward (decode) direction
    takes natural-order input and leaves bit-reversed output, the inverse
    (encode) direction takes bit-reversed input and leaves natural order,
    so no permutation happens inside a kernel (callers gather outside,
    ``bitrev_gather``). Each element sees the operands of the
    natural-order butterfly it is relabeled from, in the same op order, so
    the planes are the same bits. tw_ref: the ``stage_twiddles`` table
    (a ref or array), read one stage at a time. The inverse direction
    folds in the 1/n scale.
    """
    def stage(s, x, axis, shift):
        w = dfl.dfc_from_planes(tuple(tw_ref[s, k] for k in range(4)))
        u, v, upper = _pairs(x, shift, axis)
        if inverse:
            return _select(upper, dfl.dfc_mul(dfl.dfc_sub(u, v), w),
                           dfl.dfc_add(u, v))
        vw = dfl.dfc_mul(v, w)
        return _select(upper, dfl.dfc_sub(u, vw), dfl.dfc_add(u, vw))

    # strides double along the inverse (encode) pipeline and halve along
    # the forward one
    x = common.stage_loops(x, tw_ref.shape[0], not inverse, stage)
    if inverse:
        inv_n = 1.0 / n
        hi = np.float32(inv_n)
        lo = np.float32(inv_n - float(hi))
        scale = dfl.DF(hi, lo)
        x = dfl.DFComplex(dfl.df_mul(x.re, scale), dfl.df_mul(x.im, scale))
    return x


def _kernel(rh_ref, rl_ref, ih_ref, il_ref, tw_ref,
            orh, orl, oih, oil, *, n, inverse):
    x = dfl.dfc_from_planes(
        (rh_ref[...], rl_ref[...], ih_ref[...], il_ref[...]))
    x = fft_stage_pipeline(x, tw_ref, n=n, inverse=inverse)
    orh[...], orl[...], oih[...], oil[...] = dfl.dfc_to_planes(x)


def _run(planes, m: int, block_rows: int, inverse: bool, interpret: bool):
    """One pallas_call over tiled (rows, R, C) views of (rows, n) planes."""
    rows, n = planes[0].shape
    tw = stage_twiddles(n, m, inverse)
    tile = tw.shape[-2:]
    body = functools.partial(_kernel, n=n, inverse=inverse)
    grid, block_rows = common.row_grid(rows, block_rows)
    dspec = pl.BlockSpec((block_rows,) + tile, lambda i: (i, 0, 0))
    tspec = pl.BlockSpec(tw.shape, lambda i: (0, 0, 0, 0))
    shape = jax.ShapeDtypeStruct((rows,) + tile, jnp.float32)
    out = pl.pallas_call(
        body,
        grid=grid,
        in_specs=[dspec] * 4 + [tspec],
        out_specs=(dspec,) * 4,
        out_shape=(shape,) * 4,
        interpret=interpret,
    )(*(p.reshape((rows,) + tile) for p in planes), jnp.asarray(tw))
    return tuple(o.reshape(rows, n) for o in out)


# ---------------------------------------------------------------------------
# Jit-traceable plane entry points (the device-resident client path)
# ---------------------------------------------------------------------------


def special_fft_planes(planes, m: int, block_rows: int = 1,
                       interpret: bool = True):
    """Decode-direction transform on four (rows, n) f32 df planes.

    Fully jit-traceable: the bit-reversal is a jnp gather outside the
    kernel and the pallas_call traces into the surrounding jit, so no host
    complex128 array is ever materialised.
    """
    return bitrev_gather(_run(planes, m, block_rows, False, interpret))


def special_ifft_planes(planes, m: int, block_rows: int = 1,
                        interpret: bool = True):
    """Encode-direction transform (includes 1/n) on df planes; traceable."""
    return _run(bitrev_gather(planes), m, block_rows, True, interpret)


# ---------------------------------------------------------------------------
# complex128 wrappers (host entry/exit around the plane layer)
# ---------------------------------------------------------------------------


def _to_planes(z: np.ndarray):
    return dfl.dfc_to_planes(dfl.dfc_from_parts(z.real, z.imag))


def _from_planes(planes):
    w = dfl.dfc_from_planes(planes)
    return (np.asarray(dfl.df_to_float(w.re))
            + 1j * np.asarray(dfl.df_to_float(w.im)))


def special_fft_rows(z: np.ndarray, m: int, block_rows: int = 1,
                     interpret: bool = True) -> np.ndarray:
    """Decode-direction transform of (rows, n) complex, df32 kernel."""
    z = np.asarray(z, np.complex128)
    out = special_fft_planes(_to_planes(z), m, block_rows=block_rows,
                             interpret=interpret)
    return _from_planes(out)


def special_ifft_rows(z: np.ndarray, m: int, block_rows: int = 1,
                      interpret: bool = True) -> np.ndarray:
    """Encode-direction transform (includes 1/n), df32 kernel."""
    z = np.asarray(z, np.complex128)
    out = special_ifft_planes(_to_planes(z), m, block_rows=block_rows,
                              interpret=interpret)
    return _from_planes(out)
