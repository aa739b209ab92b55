"""Double-word floating-point arithmetic (Dekker/Knuth error-free transforms).

ABC-FHE's Fourier engine uses a custom FP55 format (1+11+43) because >= 43
mantissa bits keep bootstrapping precision above the 19.29-bit requirement
(paper Fig. 3c). TPUs have no fp64 and no FP55; the TPU-idiomatic substitute
is *double-float32* — an unevaluated (hi, lo) pair of f32 giving ~49
effective mantissa bits, built entirely from native f32 VPU ops. This module
implements the error-free transforms generically so the same code runs as

  * df32 (pairs of f32)  — the kernel datapath (>= 43 bits, Fig. 3c-valid);
  * df64 (pairs of f64)  — ~106-bit CPU oracle used for exact encode
    rounding and CRT recombination of double-scale (≈2^60) values.

No FMA is assumed (TPU VPU has none exposed): TwoProd uses Dekker/Veltkamp
splitting.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp


class DF(NamedTuple):
    """Unevaluated sum hi + lo with |lo| <= ulp(hi)/2."""

    hi: jnp.ndarray
    lo: jnp.ndarray

    @property
    def dtype(self):
        return self.hi.dtype


def _split_const(dtype) -> float:
    # Veltkamp splitter: 2^ceil(p/2) + 1 for p-bit mantissa
    if jnp.dtype(dtype) == jnp.float32:
        return float(2 ** 12 + 1)
    return float(2 ** 27 + 1)


def df_from(x, dtype=jnp.float32) -> DF:
    x = jnp.asarray(x)
    hi = x.astype(dtype)
    lo = (x - hi.astype(x.dtype)).astype(dtype) if x.dtype != dtype else jnp.zeros_like(hi)
    return DF(hi, lo)


def df_const(value: float, dtype=jnp.float32) -> DF:
    """Split a python float (f64) into a df constant of the target dtype.
    The split happens in numpy so the function stays jit-traceable."""
    hi = np.asarray(value, jnp.dtype(dtype))
    lo = np.asarray(value - float(hi), jnp.dtype(dtype))
    return DF(jnp.asarray(hi), jnp.asarray(lo))


def two_sum(a, b):
    s = a + b
    bb = s - a
    err = (a - (s - bb)) + (b - bb)
    return s, err


def quick_two_sum(a, b):
    """Requires |a| >= |b|."""
    s = a + b
    return s, b - (s - a)


def _split(a):
    """Dekker split a = hi + lo with both halves short enough that every
    partial product is exact. f32 truncates the mantissa with a bit mask
    (12 + 12 bits): no multiply, so no compiler can contract the split
    into a fused multiply-add and change it. f64 keeps Veltkamp's
    splitter (53 bits do not truncate into two 26-bit halves)."""
    if jnp.dtype(a.dtype) == jnp.float32:
        if isinstance(a, (np.ndarray, np.generic)):     # a literal constant
            hi = (np.asarray(a).view(np.uint32)
                  & np.uint32(0xFFFFF000)).view(np.float32)
            return hi, a - hi
        bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
        hi = jax.lax.bitcast_convert_type(bits & np.uint32(0xFFFFF000),
                                          jnp.float32)
        return hi, a - hi
    # numpy scalar (not jnp) so Pallas kernels see a literal, not a capture
    c = jnp.dtype(a.dtype).type(_split_const(a.dtype))
    hi = c * a - (c * a - a)
    return hi, a - hi


def _uncontracted(p, other):
    """The rounded product p, opaque to multiply-add contraction: a select
    the compiler cannot fold (its arms differ, and it picks `other` only
    for a NaN p) stands between the multiply and the add that consumes
    it. XLA's CPU backend otherwise fuses ``e + x*y`` into one FMA, which
    rounds once where the chip (and IEEE op-by-op evaluation) rounds
    twice."""
    return jnp.where(p != p, other, p)


def two_prod(a, b):
    """Error-free a*b = p + e via Dekker's product (no FMA). The partial
    products are exact, so e is the unique rounding error of p however
    the compiler schedules them. p itself is kept out of contraction:
    ``p + e`` fused into fma(a, b, e) would round the exact product."""
    p = _uncontracted(a * b, a)
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    e = ((a_hi * b_hi - p) + a_hi * b_lo + a_lo * b_hi) + a_lo * b_lo
    return p, e


def df_add(x: DF, y: DF) -> DF:
    s, e = two_sum(x.hi, y.hi)
    e = e + x.lo + y.lo
    return DF(*quick_two_sum(s, e))


def df_sub(x: DF, y: DF) -> DF:
    return df_add(x, DF(-y.hi, -y.lo))


def df_mul(x: DF, y: DF) -> DF:
    p, e = two_prod(x.hi, y.hi)
    e = (e + _uncontracted(x.hi * y.lo, x.lo)
         + _uncontracted(x.lo * y.hi, y.lo))
    return DF(*quick_two_sum(p, e))


def df_neg(x: DF) -> DF:
    return DF(-x.hi, -x.lo)


def df_to_float(x: DF):
    """Collapse to the wider native float (f64 on CPU) for verification."""
    return x.hi.astype(jnp.float64) + x.lo.astype(jnp.float64)


def df_round(x: DF) -> DF:
    """Round to nearest integer, keeping the (possibly > mantissa) value
    exactly as an integer-valued df pair."""
    rh = jnp.round(x.hi)
    frac = (x.hi - rh) + x.lo           # exact: |x.hi - rh| <= 0.5
    rl = jnp.round(frac)
    return DF(*quick_two_sum(rh, rl))


class DFComplex(NamedTuple):
    re: DF
    im: DF


def dfc_from(z, dtype=jnp.float32) -> DFComplex:
    return DFComplex(df_from(jnp.real(z), dtype), df_from(jnp.imag(z), dtype))


def dfc_from_parts(re, im, dtype=jnp.float32) -> DFComplex:
    """Real/imag float arrays -> DFComplex (hi = cast, lo = residual).
    jit-traceable; the device-Fourier encode entry uses it to split f64
    slot parts into df32 planes without materialising a complex array."""
    return DFComplex(df_from(jnp.asarray(re), dtype),
                     df_from(jnp.asarray(im), dtype))


def dfc_to_planes(z: DFComplex):
    """DFComplex -> the four (re_hi, re_lo, im_hi, im_lo) planes — the
    canonical kernel/BlockSpec layout of a complex df array."""
    return z.re.hi, z.re.lo, z.im.hi, z.im.lo


def dfc_from_planes(planes) -> DFComplex:
    rh, rl, ih, il = planes
    return DFComplex(DF(rh, rl), DF(ih, il))


def dfc_add(a: DFComplex, b: DFComplex) -> DFComplex:
    return DFComplex(df_add(a.re, b.re), df_add(a.im, b.im))


def dfc_sub(a: DFComplex, b: DFComplex) -> DFComplex:
    return DFComplex(df_sub(a.re, b.re), df_sub(a.im, b.im))


def dfc_mul(a: DFComplex, b: DFComplex) -> DFComplex:
    """(ac - bd) + i(ad + bc) — four df multiplies, the reconfigured
    4-multiplier complex unit of paper eq. (12)."""
    ac = df_mul(a.re, b.re)
    bd = df_mul(a.im, b.im)
    ad = df_mul(a.re, b.im)
    bc = df_mul(a.im, b.re)
    return DFComplex(df_sub(ac, bd), df_add(ad, bc))


def dfc_to_complex(a: DFComplex):
    return df_to_float(a.re) + 1j * df_to_float(a.im)


def effective_mantissa_bits(dtype) -> int:
    """Worst-case effective mantissa of a df pair (2p+1 bits)."""
    p = 24 if jnp.dtype(dtype) == jnp.float32 else 53
    return 2 * p + 1


# ---------------------------------------------------------------------------
# df32^2 (split-limb / expansion) arithmetic — the compiled-mode datapath
# ---------------------------------------------------------------------------
# The megakernel's Delta-scale / RNS / CRT interior was f64 (exact on the CPU
# interpret path, unlowerable on TPU VPUs). The df32^2 substitutes below keep
# every integer-valued intermediate as a short *expansion* of f32 components
# (an unevaluated sum, each component integer-valued) built purely from
# error-free transforms, so the same exact integers flow through the kernel
# without ever materialising a float64:
#
#   * ``df_round_rne`` — exact round-to-nearest-even of a df pair, ties and
#     parity included, returning a 3-component integer expansion. Matches
#     ``jnp.round`` of the exact pair value bit-for-bit (the f64 oracle path
#     rounds the exact value too, so the rounded integers are identical).
#   * ``expansion3_digits`` — exact balanced base-2^22 digit split of that
#     expansion (|value| < 2^63); the digits feed pure-uint32 per-limb
#     modular reduction (``rns.digits_to_residue``).
#   * ``terms4_to_df`` — collapse four non-overlapping f32 terms (the
#     16-bit-field split of a u32-pair CRT value) to a df32 pair for the
#     FFT stages.
#
# DESIGN.md §4 carries the per-stage error budget (every stage here is
# *exact*; only the final pair collapse rounds, budgeted at 2^-48
# relative — the df32 pair window).

_HALF = np.float32(0.5)
_TWO = np.float32(2.0)


def _is_odd_int(x):
    """Parity of an integer-valued float array, exact for any magnitude
    (values with ulp >= 2 are even by construction)."""
    half = x * x.dtype.type(0.5)
    return (x - _TWO.astype(x.dtype) * jnp.floor(half)) == x.dtype.type(1)


def df_round_rne(x: DF):
    """Exact round-to-nearest-even of the df pair value hi + lo.

    Returns a 3-component expansion (s, c, b) of integer-valued arrays with
    s + c + b == RNE(hi + lo) exactly — including ties (value = k + 1/2
    rounds to the even neighbour, matching what the df64 oracle's
    ``jnp.round`` does to the exact product). Pure two_sum/compare/select
    chains: no wider float is ever formed.
    """
    one = x.hi.dtype.type(1)
    half = _HALF.astype(x.hi.dtype)
    s, err = two_sum(x.hi, x.lo)            # exact: value = s + err
    rs = jnp.round(s)
    t = s - rs                              # exact (Sterbenz), |t| <= 1/2
    f, e = two_sum(t, err)                  # exact: frac = f + e
    fr = jnp.round(f)
    d = f - fr                              # exact, |d| <= 1/2
    g, h = two_sum(d, e)                    # exact: resid = g + h
    # resid in [-1/2 - ulp, 1/2 + ulp]; the only rounding boundaries are
    # +-1/2, and resid == +-1/2 exactly iff (g == +-1/2 and h == 0) (the
    # representable-gap argument: |h| <= ulp(g)/2 cannot bridge the gap).
    up = (g > half) | ((g == half) & (h > 0))
    up_tie = (g == half) & (h == 0)
    dn = (g < -half) | ((g == -half) & (h < 0))
    dn_tie = (g == -half) & (h == 0)
    odd = _is_odd_int(rs) != _is_odd_int(fr)
    zero = x.hi.dtype.type(0)
    adj = (jnp.where(up | (up_tie & odd), one, zero)
           - jnp.where(dn | (dn_tie & odd), one, zero))
    a, b = two_sum(fr, adj)                 # exact (|fr| can exceed 2^24)
    s1, c = two_sum(rs, a)
    return s1, c, b


def expansion3_digits(s, c, b):
    """Exact balanced digits (d0, d1, d2) of the integer s + c + b with
    value == d0 + d1*2^22 + d2*2^44 and |d_i| < 2^23, for |value| < 2^63.

    Digit choice is round-nearest on the *leading* component only — any
    split with bounded digits is valid (the reconstruction is an identity),
    so the slack from the unrenormalized tail just widens the digit range.
    """
    dt = s.dtype
    r44 = dt.type(2.0 ** 44)
    r44i = dt.type(2.0 ** -44)
    r22 = dt.type(2.0 ** 22)
    r22i = dt.type(2.0 ** -22)
    d2 = jnp.round(s * r44i)
    s0 = s - d2 * r44                       # exact (Sterbenz / small cases)
    # renormalize the <= 2^45 remainder so the next digit sees a true
    # leading component (c may exceed 2^22 when s was large)
    u, e2 = two_sum(c, b)
    t1, e1 = two_sum(s0, u)
    t2, t3 = two_sum(e1, e2)
    d1 = jnp.round(t1 * r22i)
    d0 = ((t1 - d1 * r22) + t2) + t3        # exact: integers < 2^24
    return d0, d1, d2


def df_mul_pow2(x: DF, scale) -> DF:
    """Exact multiply of a df pair by a power-of-two scalar."""
    s = x.hi.dtype.type(scale)
    return DF(x.hi * s, x.lo * s)


def terms4_to_df(w3, w2, w1, w0) -> DF:
    """Collapse four non-overlapping f32 terms (descending scale) into a
    df pair. The terms are exact (disjoint 16-bit fields of a u32-pair
    integer, scaled); only bits below the pair's ~49-bit window round."""
    s, e1 = two_sum(w1, w0)
    s, e2 = two_sum(w2, s)
    hi, e3 = two_sum(w3, s)
    lo = (e3 + e2) + e1
    return DF(*quick_two_sum(hi, lo))
