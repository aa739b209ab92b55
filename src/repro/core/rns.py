"""RNS decomposition / CRT recombination for the CKKS client.

Client-side needs only two directions (paper Fig. 2a):
  * encode:  integer-valued df64 coefficients  -> residues mod each q_i
  * decode:  residues of the 2 decrypt limbs   -> centered value / Delta

Both use exact float tricks (fmod on integer-valued doubles is error-free;
products < 2^53 per word are kept exact via error-free transforms), so no
big-integer arithmetic appears on the hot path. An exact Python-int oracle
is provided for property tests.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core import dfloat as dfl
from repro.core import modmul


def to_rns_df(x: dfl.DF, q_list: tuple[int, ...]) -> jnp.ndarray:
    """Integer-valued df64 (hi, lo) -> (L, ...) uint32 residues.

    hi and lo are integer-valued float64 with |lo| <= ulp(hi)/2; fmod of an
    integer-valued double by q < 2^31 is exact, so each limb residue is an
    exact function of the true integer hi + lo.

    The limb loop is a single broadcasted pass: q_list becomes a (L, 1, ...)
    array against (…,)-shaped hi/lo, producing all residues at once (the
    batched-client SoA layout). Elementwise fmod is unchanged, so results
    stay bit-identical to the per-limb loop.
    """
    qf = jnp.asarray(np.asarray(q_list, np.float64).reshape(
        (len(q_list),) + (1,) * jnp.ndim(x.hi)))
    r = jnp.fmod(x.hi[None], qf) + jnp.fmod(x.lo[None], qf)   # in (-2q, 2q)
    r = jnp.fmod(r, qf)
    r = jnp.where(r < 0, r + qf, r)
    return r.astype(jnp.uint32)


def to_rns_limb_t(x: dfl.DF, qf) -> jnp.ndarray:
    """One limb of ``to_rns_df`` with a TRACED modulus: qf is a float64
    scalar (e.g. read from the streaming megakernel's SMEM constant table
    and cast). Same fmod/where sequence as the broadcasted pass — fmod is
    elementwise, so the residues are bit-identical per limb."""
    r = jnp.fmod(x.hi, qf) + jnp.fmod(x.lo, qf)               # in (-2q, 2q)
    r = jnp.fmod(r, qf)
    r = jnp.where(r < 0, r + qf, r)
    return r.astype(jnp.uint32)


def crt2_to_df(c0, c1, q0: int, q1: int) -> dfl.DF:
    """Two-limb CRT -> centered integer value as an exact df64 pair.

    x = [c0 * g0]_{q0} * q1 + [c1 * g1]_{q1} * q0  (mod Q),  Q = q0*q1,
    with g_i = (Q/q_i)^{-1} mod q_i. Each product t_i * q_j < 2^62 is made
    exact with two_prod; the sum and the conditional Q-subtractions stay in
    df64 (106-bit) arithmetic. Returns centered representative in (-Q/2, Q/2).
    """
    g0 = pow(q1 % q0, -1, q0)
    g1 = pow(q0 % q1, -1, q1)
    t0 = (c0.astype(jnp.uint64) * jnp.uint64(g0)) % jnp.uint64(q0)
    t1 = (c1.astype(jnp.uint64) * jnp.uint64(g1)) % jnp.uint64(q1)
    a = _prod_df(t0.astype(jnp.float64), float(q1))
    b = _prod_df(t1.astype(jnp.float64), float(q0))
    v = dfl.df_add(a, b)                      # < 2Q
    qq = q0 * q1
    v = _cond_sub(v, float(qq))               # mod Q
    # center
    half = float(qq) / 2.0
    over = v.hi > half
    vq = dfl.df_sub(v, dfl.df_const(float(qq), jnp.float64))
    return dfl.DF(jnp.where(over, vq.hi, v.hi), jnp.where(over, vq.lo, v.lo))


def _prod_df(a, b: float):
    hi, lo = dfl.two_prod(a, jnp.asarray(b, jnp.float64))
    return dfl.DF(hi, lo)


def _cond_sub(v: dfl.DF, q: float) -> dfl.DF:
    over = v.hi >= q
    vq = dfl.df_sub(v, dfl.df_const(q, jnp.float64))
    return dfl.DF(jnp.where(over, vq.hi, v.hi), jnp.where(over, vq.lo, v.lo))


# ---------------------------------------------------------------------------
# df32/uint32 datapath (dtype_path='df32'): compiled-mode substitutes
# ---------------------------------------------------------------------------
# The f64 paths above are exact but unlowerable on TPU VPUs (no float64, no
# uint64). The substitutes below carry the SAME integers through pure
# f32/int32/uint32 arithmetic: Delta-scaled coefficients arrive as exact
# balanced base-2^22 digits (``dfloat.df_round_rne`` + ``expansion3_digits``)
# and reduce per limb with u32 Montgomery multiplies; the decode CRT runs
# entirely on u32 word pairs (16-bit limb products) and only becomes float
# at the final /Delta pair collapse. Every stage is exact, so residues and
# centered values are bit-identical to the f64 oracle per limb/element.

DIGIT_BITS = 22

_DIGIT_CONSTS_MEMO: dict[int, tuple[int, int]] = {}
_CRT2_CONSTS_MEMO: dict[tuple[int, int], dict] = {}


def digit_consts(q: int) -> tuple[int, int]:
    """Montgomery-form radix constants (2^22 mod q, 2^44 mod q) so a digit
    multiply is one REDC: REDC(d * c22_mont) = d * 2^22 mod q."""
    cached = _DIGIT_CONSTS_MEMO.get(q)
    if cached is None:
        r = 1 << 32
        cached = (((1 << DIGIT_BITS) * r) % q, ((1 << 2 * DIGIT_BITS) * r) % q)
        _DIGIT_CONSTS_MEMO[q] = cached
    return cached


def _digit_residue(d, q):
    """Signed int32 digit in (-2^23, 2^23) -> uint32 residue (|d| < q)."""
    if isinstance(q, (int, np.integer)):
        qi = np.int32(q)
    else:
        qi = jnp.asarray(q).astype(jnp.int32)
    return jnp.where(d < 0, d + qi, d).astype(jnp.uint32)


def digits_to_residue(d0, d1, d2, q, qinv_neg, c22_mont, c44_mont):
    """(d0 + d1*2^22 + d2*2^44) mod q on the uint32 limb datapath.

    Digits are int32 with |d| < 2^23; q/qinv_neg/c*_mont may be Python ints
    (static kernel closures), traced scalars (SMEM table reads) or stacked
    (L, 1, ..) arrays (the broadcasted staged path). Exact, hence
    bit-identical to ``to_rns_limb_t`` of the same integer.
    """
    r0 = _digit_residue(d0, q)
    m1 = modmul.mulmod_montgomery_limb_t(_digit_residue(d1, q), c22_mont,
                                         q, qinv_neg)
    m2 = modmul.mulmod_montgomery_limb_t(_digit_residue(d2, q), c44_mont,
                                         q, qinv_neg)
    return modmul.addmod(modmul.addmod(r0, m1, q), m2, q)


def digits_to_residues_stacked(d0, d1, d2, q_list) -> jnp.ndarray:
    """All limbs at once: digits (..., N) -> (L, ..., N) uint32 residues
    (the df32 analogue of the broadcasted ``to_rns_df`` pass)."""
    L = len(q_list)
    shape = (L,) + (1,) * d0.ndim
    r = 1 << 32
    q = np.asarray(q_list, np.uint32).reshape(shape)
    qinv = np.asarray([(-pow(int(qi), -1, r)) % r for qi in q_list],
                      np.uint32).reshape(shape)
    c22 = np.asarray([digit_consts(int(qi))[0] for qi in q_list],
                     np.uint32).reshape(shape)
    c44 = np.asarray([digit_consts(int(qi))[1] for qi in q_list],
                     np.uint32).reshape(shape)
    return digits_to_residue(d0[None], d1[None], d2[None], q, qinv, c22, c44)


def crt2_consts(q0: int, q1: int) -> dict:
    """Static constants of the uint32 two-limb CRT. ``q_w``/``half_w`` are
    the u32 word pairs of fl64(q0*q1) — the df64 oracle reduces modulo the
    ROUNDED product (``crt2_to_df`` subtracts ``float(qq)``), and the df32
    path follows the same convention so both center identically."""
    key = (q0, q1)
    cached = _CRT2_CONSTS_MEMO.get(key)
    if cached is None:
        r = 1 << 32
        qq = int(float(q0 * q1))              # fl64(Q), the oracle modulus
        half = qq // 2                        # v > Q/2 <=> v > floor(Q/2)
        cached = {
            "g0_mont": (pow(q1 % q0, -1, q0) * r) % q0,
            "g1_mont": (pow(q0 % q1, -1, q1) * r) % q1,
            "qinv0": (-pow(q0, -1, r)) % r,
            "qinv1": (-pow(q1, -1, r)) % r,
            "q_w": (qq >> 32, qq & 0xFFFFFFFF),
            "half_w": (half >> 32, half & 0xFFFFFFFF),
        }
        _CRT2_CONSTS_MEMO[key] = cached
    return cached


def crt2_centered_u32(c0, c1, q0: int, q1: int):
    """Two-limb CRT -> centered value as (sign, hi, lo): pure uint32.

    value = sign * (hi*2^32 + lo), the same centered representative the
    df64 oracle computes (fl64(Q) reduction convention included): residue
    recombination via u32 Montgomery multiplies, the 62-bit products and
    sums on u32 word pairs (16-bit limb arithmetic) — no uint64 anywhere.
    """
    k = crt2_consts(q0, q1)
    t0 = modmul.mulmod_montgomery_limb_t(
        c0, np.uint32(k["g0_mont"]), np.uint32(q0), np.uint32(k["qinv0"]))
    t1 = modmul.mulmod_montgomery_limb_t(
        c1, np.uint32(k["g1_mont"]), np.uint32(q1), np.uint32(k["qinv1"]))
    h0, l0 = modmul.mul32x32(t0, np.uint32(q1))
    h1, l1 = modmul.mul32x32(t1, np.uint32(q0))
    hi, lo = modmul._add64(h0, l0, h1, l1)               # < 2Q < 2^63
    qh, ql = np.uint32(k["q_w"][0]), np.uint32(k["q_w"][1])
    over = modmul._ge64(hi, lo, qh, ql)
    sh, sl = modmul._sub64(hi, lo, qh, ql)
    hi = jnp.where(over, sh, hi)
    lo = jnp.where(over, sl, lo)
    # center: v > Q/2 -> v - Q (sign/magnitude; the freak v >= Q leftover
    # of the single conditional subtraction keeps its positive difference,
    # exactly as the oracle's signed df64 subtraction does)
    hh, hl = np.uint32(k["half_w"][0]), np.uint32(k["half_w"][1])
    gt = modmul._gt64(hi, lo, hh, hl)
    geq = modmul._ge64(hi, lo, qh, ql)
    dh, dl = modmul._sub64(hi, lo, qh, ql)               # v - Q  (v >= Q)
    nh, nl = modmul._sub64(qh, ql, hi, lo)               # Q - v  (v <  Q)
    neg = gt & ~geq
    out_h = jnp.where(neg, nh, jnp.where(gt & geq, dh, hi))
    out_l = jnp.where(neg, nl, jnp.where(gt & geq, dl, lo))
    sign = jnp.where(neg, np.float32(-1.0), np.float32(1.0))
    return sign, out_h, out_l


def centered_to_df(sign, hi, lo, inv_scale) -> dfl.DF:
    """(sign, u32 word pair) * inv_scale -> df32 pair for the FFT stages.

    The word pair splits into four exact non-overlapping f32 terms (16-bit
    fields); the power-of-two 1/scale multiplies each term exactly; only
    the final pair collapse rounds (budget 2^-48 relative — the df32 pair
    window; DESIGN.md §4)."""
    f32 = jnp.float32
    s16 = np.float32(2.0 ** 16)
    s32 = np.float32(2.0 ** 32)
    s48 = np.float32(2.0 ** 48)
    mask = np.uint32(0xFFFF)
    s = sign * inv_scale                                 # +-2^-k, exact

    def field(x):
        # 16-bit fields go through int32: exact, and the chip has no
        # direct uint32 -> float32 conversion
        return x.astype(jnp.int32).astype(f32)

    w0 = field(lo & mask) * s
    w1 = field(lo >> 16) * s16 * s
    w2 = field(hi & mask) * s32 * s
    w3 = field(hi >> 16) * s48 * s
    return dfl.terms4_to_df(w3, w2, w1, w0)


# --- key-switch decomposition (server-side eval kernels) -------------------
#
# Hybrid key switching decomposes a polynomial per source limb: the residue
# mod q_j is centered to a signed digit D_j with |D_j| <= q_j/2 < 2^30, then
# base-extended to every modulus row (ciphertext primes + the special prime
# P).  Because every prime in the eq.(8) family sits in [2^30, 2^31), the
# centered digit's magnitude is below EVERY target modulus — base extension
# is one conditional add, no reduction.  Both helpers take traced moduli so
# one kernel body serves all limb rows, and both are pure int32/uint32 (the
# df32 datapath compiles them with JAX_ENABLE_X64=0).


def ks_center_t(v, q):
    """uint32 residues in [0, q) -> centered int32 in (-q/2, q/2].

    q odd (an NTT prime), so there are no ties: values strictly above
    (q-1)/2 = q >> 1 map down by q."""
    q = jnp.asarray(q, jnp.uint32)
    vi = v.astype(jnp.int32)
    return jnp.where(v > (q >> jnp.uint32(1)), vi - q.astype(jnp.int32), vi)


def ks_residue_t(w, q):
    """Centered int32 digit |w| < q -> uint32 residue mod q (exact single
    conditional add; the caller guarantees |w| <= q_src/2 < 2^30 <= q)."""
    q = jnp.asarray(q, jnp.uint32)
    return jnp.where(w < 0, w + q.astype(jnp.int32), w).astype(jnp.uint32)


# --- exact oracles (tests only) --------------------------------------------


def to_rns_exact(values: list[int], q_list: tuple[int, ...]) -> np.ndarray:
    return np.array(
        [[v % q for v in values] for q in q_list], dtype=np.uint32
    )


def crt_exact(residues: np.ndarray, q_list: tuple[int, ...]) -> list[int]:
    """Full CRT to centered Python ints; residues: (L, N)."""
    import math
    qq = math.prod(q_list)
    n = residues.shape[1]
    out = []
    basis = []
    for i, q in enumerate(q_list):
        m = qq // q
        basis.append(m * pow(m % q, -1, q))
    for j in range(n):
        v = sum(int(residues[i, j]) * basis[i] for i in range(len(q_list))) % qq
        if v > qq // 2:
            v -= qq
        out.append(v)
    return out
