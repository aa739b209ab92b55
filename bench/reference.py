"""Plain CKKS client reference: keygen, encode, encrypt, decrypt, decode.

Straight NumPy, written from the scheme's definition and independent of
the code under test (it imports nothing of ``repro``). What it shares
with the system is only what a server would have to know to read the
ciphertexts: the deployment's parameters, as its configuration file
states them.

- Ring R_q = Z_q[X]/(X^N + 1), one residue row per modulus of the
  configuration's ``moduli``.
- NTT domain: the residue row of a polynomial a holds, at position j,
  a(psi^(2*brv(j)+1)), with brv the bit reversal of log2(N) bits and psi
  = g^((q-1)/2N) for the least integer g >= 2 that makes psi a primitive
  2N-th root of unity (psi^N = -1 mod q).
- Canonical embedding (HEAAN order): slot j of n = N/2 slots is
  m(xi^(5^j)), xi = exp(2*pi*i/2N); coefficient k < n is Re(w_k) and
  coefficient n + k is Im(w_k), where z_j = sum_k w_k xi^(5^j k).
- Randomness: Philox-4x32-10, key and counter prefix from the 128-bit
  client seed; stream ids as the configuration's ``prng_streams`` give
  them (secret, public a, public e, and per nonce v, e0, e1).
- Public-key encryption: ct = (v*b + e0 + round(Delta*m), v*a + e1) with
  b = e - a*s, a drawn uniform directly in the NTT domain.

``Reference(config, precision=...)`` runs the Fourier and /Delta steps in
float64 (the configuration's precision) or float32 (the control).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

U64 = np.uint64
MASK32 = 0xFFFFFFFF
# rows per task and worker threads of the transforms: NumPy releases the
# interpreter lock in its loops, and rows of 2^16 words stay in cache
ROWS_PER_TASK = 4
WORKERS = 8

# ---------------------------------------------------------------------------
# Philox-4x32-10 and the samplers
# ---------------------------------------------------------------------------

_M0, _M1 = 0xD2511F53, 0xCD9E8D57
_W0, _W1 = 0x9E3779B9, 0xBB67AE85


def philox(stream: int, seed: int, n: int, word: int) -> np.ndarray:
    """First output word of Philox-4x32-10 at counters
    (i, stream, word ^ seed[64:96], seed[96:128]) for i < n, keyed by
    seed[0:64]."""
    parts = [(seed >> (32 * i)) & MASK32 for i in range(4)]
    k0, k1 = parts[0], parts[1]
    c0 = np.arange(n, dtype=U64)
    c1 = np.full(n, stream & MASK32, U64)
    c2 = np.full(n, (word ^ parts[2]) & MASK32, U64)
    c3 = np.full(n, parts[3], U64)
    m0, m1, mask = U64(_M0), U64(_M1), U64(MASK32)
    for _ in range(10):
        p0 = c0 * m0
        p1 = c2 * m1
        c0, c1, c2, c3 = ((p1 >> U64(32)) ^ c1 ^ U64(k0), p1 & mask,
                          (p0 >> U64(32)) ^ c3 ^ U64(k1), p0 & mask)
        k0, k1 = (k0 + _W0) & MASK32, (k1 + _W1) & MASK32
    return c0


def ternary(seed, stream, n):
    u = philox(stream, seed, n, 0)
    third = U64(0x55555555)
    return np.where(u < third, 1, np.where(u < 2 * third, -1, 0)).astype(
        np.int64)


def zero_one(seed, stream, n):
    """{-1, 0, 1} with P(+-1) = 1/4 each."""
    u = philox(stream, seed, n, 0)
    return np.where(u < U64(1 << 30), 1,
                    np.where(u < U64(1 << 31), -1, 0)).astype(np.int64)


def _popcount21(x):
    x = x & U64((1 << 21) - 1)
    bits = np.unpackbits(x.astype("<u4").view(np.uint8).reshape(-1, 4),
                         axis=1)
    return bits.sum(axis=1).astype(np.int64)


def cbd21(seed, stream, n):
    """Centered binomial, eta = 21."""
    return (_popcount21(philox(stream, seed, n, 0))
            - _popcount21(philox(stream, seed, n, 1)))


def uniform_mod(seed, stream, n, q):
    """(hi * 2^32 + lo) mod q of two Philox words."""
    hi = philox(stream, seed, n, 0)
    lo = philox(stream, seed, n, 1)
    return ((hi << U64(32)) | lo) % U64(q)


# ---------------------------------------------------------------------------
# number theory and the NTT
# ---------------------------------------------------------------------------


def is_prime(n: int) -> bool:
    if n < 2:
        return False
    for p in (2, 3, 5, 7, 11, 13, 17, 19, 23, 29, 31, 37):
        if n % p == 0:
            return n == p
    d, r = n - 1, 0
    while d % 2 == 0:
        d //= 2
        r += 1
    for a in (2, 3, 5, 7, 11, 13, 17, 19, 23, 29, 31, 37):
        x = pow(a, d, n)
        if x in (1, n - 1):
            continue
        for _ in range(r - 1):
            x = x * x % n
            if x == n - 1:
                break
        else:
            return False
    return True


def ntt_root(q: int, n: int) -> int:
    """psi = g^((q-1)/2N) for the least g >= 2 with psi^N = -1 mod q."""
    cof = (q - 1) // (2 * n)
    for g in range(2, 1 << 20):
        psi = pow(g, cof, q)
        if pow(psi, n, q) == q - 1:
            return psi
    raise ValueError(f"no primitive 2N-th root of unity mod {q}")


def bit_reverse(n: int) -> np.ndarray:
    bits = n.bit_length() - 1
    idx = np.arange(n)
    out = np.zeros(n, np.int64)
    for b in range(bits):
        out |= ((idx >> b) & 1) << (bits - 1 - b)
    return out


def _powers(base: int, count: int, q: int) -> np.ndarray:
    out = np.empty(count, U64)
    out[0] = 1
    step, filled = base % q, 1
    while filled < count:
        take = min(filled, count - filled)
        out[filled:filled + take] = (out[:take] * U64(step)) % U64(q)
        filled += take
        step = step * step % q
    return out


class RingNTT:
    """Negacyclic transforms for a list of moduli at one ring degree, as
    the evaluation at psi^(2 brv(j) + 1): a twist by psi^k followed by a
    radix-2 cyclic transform with omega = psi^2."""

    def __init__(self, moduli, n: int):
        self.n = n
        self.q = np.array(moduli, U64)
        self.brv = bit_reverse(n)
        tw, itw, twist, itwist = [], [], [], []
        for q in moduli:
            psi = ntt_root(q, n)
            psi_inv = pow(psi, -1, q)
            tw.append(_powers(psi * psi % q, n // 2, q))
            itw.append(_powers(psi_inv * psi_inv % q, n // 2, q))
            twist.append(_powers(psi, n, q))
            inv_n = pow(n, -1, q)
            itwist.append(_powers(psi_inv, n, q) * U64(inv_n) % U64(q))
        self.tw, self.itw = np.stack(tw), np.stack(itw)
        self.twist, self.itwist = np.stack(twist), np.stack(itwist)

    def _cyclic(self, x, rows, tw):
        """Radix-2 DIT over bit-reversed input rows: natural-order
        cyclic transform of every row, modulus per row."""
        r, n = x.shape
        q = self.q[rows].reshape(r, 1, 1)
        h = 1
        while h < n:
            y = x.reshape(r, n // (2 * h), 2, h)
            w = tw[rows][:, None, ::n // (2 * h)][..., :h]
            u = y[:, :, 0, :]
            v = (y[:, :, 1, :] * w) % q
            s = u + v
            s = np.where(s >= q, s - q, s)
            d = u + q - v
            d = np.where(d >= q, d - q, d)
            x = np.stack([s, d], axis=2).reshape(r, n)
            h *= 2
        return x

    def _forward(self, a, rows):
        q = self.q[rows][:, None]
        x = (a * self.twist[rows]) % q
        return self._cyclic(x[:, self.brv], rows, self.tw)[:, self.brv]

    def _inverse(self, a, rows):
        # the NTT domain is already the bit-reversed order of the
        # evaluations, which is what the inverse cyclic transform takes
        q = self.q[rows][:, None]
        return (self._cyclic(a, rows, self.itw) * self.itwist[rows]) % q

    def _rowwise(self, fn, a, rows):
        a = np.asarray(a, U64)
        rows = np.asarray(rows)
        starts = range(0, len(rows), ROWS_PER_TASK)
        with ThreadPoolExecutor(WORKERS) as ex:
            parts = list(ex.map(
                lambda i: fn(a[i:i + ROWS_PER_TASK],
                             rows[i:i + ROWS_PER_TASK]), starts))
        return np.concatenate(parts)

    def forward(self, a, rows):
        """(R, N) residues in coefficient order -> NTT domain; ``rows``
        gives each row's modulus index."""
        return self._rowwise(self._forward, a, rows)

    def inverse(self, a, rows):
        """NTT domain -> (R, N) residues in coefficient order."""
        return self._rowwise(self._inverse, a, rows)


def residues(x, moduli) -> np.ndarray:
    """Signed integers (N,) -> (L, N) residues."""
    x = np.asarray(x, np.int64)
    return np.stack([np.mod(x, q) for q in moduli]).astype(U64)


def centered(r, q):
    r = np.asarray(r, np.int64)
    q = np.asarray(q, np.int64)
    return np.where(r > q // 2, r - q, r)


# ---------------------------------------------------------------------------
# the reference client
# ---------------------------------------------------------------------------


class Reference:
    """Keygen, encode+encrypt check and decrypt+decode of one
    configuration. ``limbs`` bounds the moduli it builds transforms for
    (2 suffices for decryption)."""

    def __init__(self, config: dict, precision: str = "float64",
                 limbs: int | None = None):
        if precision not in ("float64", "float32"):
            raise ValueError(f"precision must be float64 or float32, got "
                             f"{precision!r}")
        self.precision = precision
        self.n = 1 << int(config["logn"])
        self.n_slots = self.n // 2
        moduli = [int(q) for q in config["moduli"]]
        if len(moduli) != int(config["n_limbs"]):
            raise ValueError("the configuration lists "
                             f"{len(moduli)} moduli for "
                             f"{config['n_limbs']} limbs")
        for q in moduli:
            if not is_prime(q) or (q - 1) % (2 * self.n):
                raise ValueError(f"modulus {q} is not a prime = 1 mod 2N")
        self.limbs = len(moduli) if limbs is None else int(limbs)
        self.moduli = moduli[:self.limbs]
        self.delta = float(2 ** int(config["delta_bits"]))
        self.seed = int(str(config["seed"]), 0)
        self.streams = {k: int(str(v), 0)
                        for k, v in config["prng_streams"].items()}
        self.ntt = RingNTT(self.moduli, self.n)
        self.rows = np.arange(self.limbs)
        # slot j <-> evaluation at xi^(5^j); 5^j = 4 u_j + 1 (mod 2N)
        five = np.empty(self.n_slots, np.int64)
        g = 1
        for j in range(self.n_slots):
            five[j] = g
            g = g * 5 % (2 * self.n)
        self.u = (five - 1) // 4
        self.keys = None

    # --- keys -----------------------------------------------------------

    def keygen(self):
        """(s_hat, a_hat, b_hat), each (limbs, N), NTT domain."""
        if self.keys is None:
            st, n = self.streams, self.n
            s = ternary(self.seed, st["secret"], n)
            a_hat = np.stack([uniform_mod(self.seed, st["public_a"] + i, n,
                                          q)
                              for i, q in enumerate(self.moduli)])
            e = cbd21(self.seed, st["public_e"], n)
            s_hat = self.ntt.forward(residues(s, self.moduli), self.rows)
            e_hat = self.ntt.forward(residues(e, self.moduli), self.rows)
            q = self.ntt.q[:, None]
            b_hat = (e_hat + q - (a_hat * s_hat) % q) % q
            self.keys = (s_hat, a_hat, b_hat)
        return self.keys

    def draws(self, nonce: int):
        """(v, e0, e1) signed coefficients of one encryption."""
        st, n, off = self.streams, self.n, 16 * int(nonce)
        return (zero_one(self.seed, st["enc_v"] + off, n),
                cbd21(self.seed, st["enc_e0"] + off, n),
                cbd21(self.seed, st["enc_e1"] + off, n))

    # --- the canonical embedding ----------------------------------------

    def _cdtype(self):
        return np.complex128 if self.precision == "float64" else np.complex64

    def _xi(self, sign: int):
        k = np.arange(self.n_slots)
        return np.exp(sign * 1j * np.pi * k / self.n).astype(self._cdtype())

    def encode_coeffs(self, z) -> np.ndarray:
        """(n,) complex slots -> (N,) integer coefficients round(Delta m)."""
        z = np.asarray(z, self._cdtype())
        zz = np.zeros(self.n_slots, self._cdtype())
        zz[self.u] = z
        w = np.fft.fft(zz) * self._xi(-1) / self._cdtype()(self.n_slots)
        m = np.concatenate([w.real, w.imag])
        return np.rint(m * m.dtype.type(self.delta)).astype(np.int64)

    def decode_coeffs(self, v) -> np.ndarray:
        """(N,) integer coefficients -> (n,) complex slots of v / Delta."""
        real = np.float64 if self.precision == "float64" else np.float32
        x = np.asarray(v).astype(real) / real(self.delta)
        w = (x[:self.n_slots] + 1j * x[self.n_slots:]).astype(self._cdtype())
        y = np.fft.ifft(w * self._xi(1)) * self._cdtype()(self.n_slots)
        return y[self.u].astype(np.complex128)

    # --- encryption -----------------------------------------------------

    def encrypt(self, z, nonce: int, limbs: int | None = None):
        """(c0, c1), each (limbs, N) uint64 residues in the NTT domain."""
        limbs = self.limbs if limbs is None else limbs
        rows = self.rows[:limbs]
        moduli = self.moduli[:limbs]
        s_hat, a_hat, b_hat = (k[:limbs] for k in self.keygen())
        v, e0, e1 = self.draws(nonce)
        pt = self.encode_coeffs(z)
        polys = np.concatenate([residues(x, moduli) for x in (v, e0, e1, pt)])
        hat = self.ntt.forward(polys, np.tile(rows, 4))
        v_h, e0_h, e1_h, pt_h = (hat[i * limbs:(i + 1) * limbs]
                                 for i in range(4))
        q = self.ntt.q[:limbs, None]
        c0 = ((v_h * b_hat) % q + e0_h + pt_h) % q
        c1 = ((v_h * a_hat) % q + e1_h) % q
        return c0, c1

    def encrypt_gaps(self, z, nonce: int, c0, c1) -> dict:
        """How far a served (c0, c1) for message z under ``nonce`` lies
        from this reference: ``c1_mismatch`` counts coefficients of
        INTT(c1 - v*a) that differ from e1; ``c0_gap`` is the largest
        |d| over limbs and coefficients, d = INTT(c0 - v*b) - e0 -
        round(Delta m) centered mod each q, in units of 1/Delta. A served
        ciphertext with a wrong nonce, limb or row reads far off in both.
        """
        s_hat, a_hat, b_hat = self.keygen()
        L = self.limbs
        q = self.ntt.q[:, None]
        v, e0, e1 = self.draws(nonce)
        v_h = self.ntt.forward(residues(v, self.moduli), self.rows)
        c0 = np.asarray(c0, U64).reshape(L, self.n)
        c1 = np.asarray(c1, U64).reshape(L, self.n)
        d0 = (c0 + q - (v_h * b_hat) % q) % q
        d1 = (c1 + q - (v_h * a_hat) % q) % q
        coef = self.ntt.inverse(np.concatenate([d0, d1]),
                                np.tile(self.rows, 2))
        qi = self.ntt.q.astype(np.int64)[:, None]
        e1_got = centered(coef[L:], qi)
        pt = self.encode_coeffs(z)
        want = (pt[None, :] + e0[None, :]) % qi
        d = centered((coef[:L].astype(np.int64) - want) % qi, qi)
        return {"c1_mismatch": int(np.count_nonzero(e1_got != e1[None, :])),
                "c0_gap": int(np.max(np.abs(d)))}

    # --- decryption -----------------------------------------------------

    def decrypt(self, c0, c1) -> np.ndarray:
        """Two-limb ciphertext (each (>=2, N)) -> (n,) complex slots."""
        s_hat = self.keygen()[0][:2]
        q = self.ntt.q[:2, None]
        c0 = np.asarray(c0, U64)[:2]
        c1 = np.asarray(c1, U64)[:2]
        m_hat = (c0 + (c1 * s_hat) % q) % q
        r = self.ntt.inverse(m_hat, self.rows[:2]).astype(object)
        q0, q1 = self.moduli[0], self.moduli[1]
        big = q0 * q1
        x = r[0] + q0 * (((r[1] - r[0]) * pow(q0, -1, q1)) % q1)
        x = np.where(x > big // 2, x - big, x)
        return self.decode_coeffs(x.astype(np.int64))
