"""Differential kernel-oracle tier for the df32^2 client datapath (ISSUE 5).

Every reduced-precision stage of the compiled-mode (datapath='df32')
pipeline is differenced against its exact f64 oracle, with a NAMED
per-stage budget asserted (``STAGE_BUDGETS``):

  * ``delta_scale_round`` — df32^2 RNE + digit split vs the df64 exact
    round: 0 ULP (the SAME integer, ties-to-even included);
  * ``rns_reduce``        — uint32 digit reduction vs exact fmod: 0 ULP;
  * ``crt_center``        — uint32 word-pair CRT vs the df64 CRT: 0 ULP
    (including the oracle's fl64(Q) reduction convention);
  * ``div_delta_pair``    — the /Delta pair collapse: <= 2^-48 relative
    (the only stage that rounds — a df32 pair holds ~49 bits).

On top of the stage oracles: hypothesis properties for the error-free
transform identities ``two_sum``/``two_prod``/``df_round_rne`` (exact
against python Fraction arithmetic), client-level bit-identity of the df32
pipelines against their f64 twins across the (N, Delta, L, B) grid, a
jaxpr scan proving the default (megakernel + df32) cores contain ZERO
float64/uint64/int64 ops, and the ``x64smoke`` subset the
JAX_ENABLE_X64=0 CI lane re-runs (plus an in-suite subprocess equivalent
that pins bit-identical ciphertexts across the two x64 modes).
"""

import hashlib
import math
import os
import subprocess
import sys
from fractions import Fraction

import numpy as np
import pytest

import jax
import jax.extend.core as jax_core
import jax.numpy as jnp

from repro.core import dfloat as dfl
from repro.core import encoder, rns
from repro.core.context import CKKSParams, get_context
from repro.fhe_client.client import FHEClient

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False


# Named per-stage error budgets (ULP of the stage's output integer, or a
# relative bound for the one stage that rounds). Asserted below; quoted in
# DESIGN.md §4's error-budget table.
STAGE_BUDGETS = {
    "delta_scale_round": 0,          # exact integers (RNE of exact product)
    "rns_reduce": 0,                 # exact residues
    "crt_center": 0,                 # exact centered integers
    "div_delta_pair": 2.0 ** -48,    # relative; df32 pair window
}

# the (N, Delta, L) grid the stage differentials sweep; B varies per test
GRID = [(5, 30, 2), (6, 45, 3), (6, 40, 3)]


def _msgs(ctx, batch, seed):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((batch, ctx.params.n_slots))
            + 1j * rng.standard_normal((batch, ctx.params.n_slots))) * 0.5


def _coeff_pairs(n, seed, scale_exp=0):
    """Synthetic df32 coefficient pairs (hi, lo) like the IFFT emits."""
    rng = np.random.default_rng(seed)
    hi = (rng.standard_normal(n) * 2.0 ** scale_exp).astype(np.float32)
    lo = (rng.standard_normal(n) * np.abs(hi) * 2.0 ** -25).astype(np.float32)
    return hi, lo


def _exact_int(*comps):
    """Exact integer value of integer-valued float components."""
    return [sum(int(c[i]) for c in comps) for i in range(len(comps[0]))]


# ---------------------------------------------------------------------------
# stage differentials: df32^2 vs the f64 oracle, per budget
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("logn,delta_bits,n_limbs", GRID)
def test_delta_scale_round_stage_zero_ulp(logn, delta_bits, n_limbs):
    """df32^2 Delta-scale + RNE digits reconstruct EXACTLY the integer the
    df64 oracle rounds to (budget: delta_scale_round = 0 ULP)."""
    delta = float(2 ** delta_bits)
    hi, lo = _coeff_pairs(1 << logn, seed=logn * 7 + delta_bits)
    pair = dfl.DF(jnp.asarray(hi), jnp.asarray(lo))
    d0, d1, d2 = encoder.delta_scale_digits(pair, delta)
    d0, d1, d2 = (np.asarray(x, np.int64) for x in (d0, d1, d2))
    got = [int(d0[i]) + int(d1[i]) * 2 ** 22 + int(d2[i]) * 2 ** 44
           for i in range(len(hi))]

    # oracle: exact df64 two_prod + round of the f64 collapse
    coeffs = jnp.asarray(hi, jnp.float64) + jnp.asarray(lo, jnp.float64)
    o = encoder.delta_scale_round(coeffs, delta)
    want = _exact_int(np.asarray(o.hi), np.asarray(o.lo))
    assert got == want, "delta_scale_round stage exceeded its 0-ULP budget"
    # digit bounds feed the uint32 reduction: |d| < 2^23 < q
    for d in (d0, d1, d2):
        assert np.max(np.abs(d)) < 2 ** 23


@pytest.mark.parametrize("logn,delta_bits,n_limbs", GRID)
def test_rns_reduce_stage_zero_ulp(logn, delta_bits, n_limbs):
    """uint32 digit reduction == exact fmod oracle residues, every limb
    (budget: rns_reduce = 0 ULP)."""
    ctx = get_context(CKKSParams(logn=logn, n_limbs=n_limbs,
                                 delta_bits=delta_bits))
    delta = ctx.params.delta
    hi, lo = _coeff_pairs(ctx.params.n, seed=3 * logn + delta_bits)
    pair = dfl.DF(jnp.asarray(hi), jnp.asarray(lo))
    digits = encoder.delta_scale_digits(pair, delta)
    got = np.asarray(rns.digits_to_residues_stacked(
        *digits, ctx.q_list[:n_limbs]))

    coeffs = jnp.asarray(hi, jnp.float64) + jnp.asarray(lo, jnp.float64)
    scaled = encoder.delta_scale_round(coeffs, delta)
    want = np.asarray(rns.to_rns_df(scaled, ctx.q_list[:n_limbs]))
    np.testing.assert_array_equal(
        got, want, err_msg="rns_reduce stage exceeded its 0-ULP budget")


@pytest.mark.parametrize("logn,delta_bits,n_limbs", GRID)
def test_crt_center_stage_zero_ulp(logn, delta_bits, n_limbs):
    """uint32 word-pair CRT == the df64 CRT's centered integers, fl64(Q)
    reduction convention included (budget: crt_center = 0 ULP)."""
    ctx = get_context(CKKSParams(logn=logn, n_limbs=n_limbs,
                                 delta_bits=delta_bits))
    q0, q1 = ctx.q_list[0], ctx.q_list[1]
    rng = np.random.default_rng(logn + delta_bits)
    m0 = rng.integers(0, q0, 1 << logn).astype(np.uint32)
    m1 = rng.integers(0, q1, 1 << logn).astype(np.uint32)

    sign, hi, lo = rns.crt2_centered_u32(jnp.asarray(m0), jnp.asarray(m1),
                                         q0, q1)
    sign, hi, lo = np.asarray(sign), np.asarray(hi), np.asarray(lo)
    got = [int(sign[i]) * (int(hi[i]) << 32 | int(lo[i]))
           for i in range(len(m0))]

    v = rns.crt2_to_df(jnp.asarray(m0).astype(jnp.uint64),
                       jnp.asarray(m1).astype(jnp.uint64), q0, q1)
    want = _exact_int(np.asarray(v.hi), np.asarray(v.lo))
    assert got == want, "crt_center stage exceeded its 0-ULP budget"


@pytest.mark.parametrize("logn,delta_bits,n_limbs", GRID)
def test_div_delta_pair_stage_budget(logn, delta_bits, n_limbs):
    """The /Delta pair collapse — the ONLY rounding stage — stays inside
    its named relative budget (div_delta_pair = 2^-48) against the exact
    rational value."""
    ctx = get_context(CKKSParams(logn=logn, n_limbs=n_limbs,
                                 delta_bits=delta_bits))
    q0, q1 = ctx.q_list[0], ctx.q_list[1]
    rng = np.random.default_rng(2 * logn + delta_bits)
    m0 = rng.integers(0, q0, 256).astype(np.uint32)
    m1 = rng.integers(0, q1, 256).astype(np.uint32)
    sign, hi, lo = rns.crt2_centered_u32(jnp.asarray(m0), jnp.asarray(m1),
                                         q0, q1)
    inv = jnp.float32(1.0) / jnp.float32(ctx.params.delta)
    x = rns.centered_to_df(sign, hi, lo, inv)
    xh = np.asarray(x.hi, np.float64)
    xl = np.asarray(x.lo, np.float64)
    signN, hiN, loN = np.asarray(sign), np.asarray(hi), np.asarray(lo)
    budget = STAGE_BUDGETS["div_delta_pair"]
    for i in range(len(m0)):
        exact = Fraction(int(signN[i]) * (int(hiN[i]) << 32 | int(loN[i])),
                         int(ctx.params.delta))
        got = Fraction(float(xh[i])) + Fraction(float(xl[i]))
        if exact == 0:
            assert got == 0
            continue
        rel = abs((got - exact) / exact)
        assert rel <= budget, (
            f"div_delta_pair stage exceeded its {budget} relative budget: "
            f"{float(rel)} at element {i}")


# ---------------------------------------------------------------------------
# hypothesis properties: error-free transform identities (core/dfloat.py)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    _SETTINGS = dict(
        deadline=None, max_examples=50, derandomize=True,
        suppress_health_check=[HealthCheck.function_scoped_fixture],
    )
    finite_f32 = st.floats(allow_nan=False, allow_infinity=False,
                           min_value=-2.0 ** 60, max_value=2.0 ** 60,
                           width=32)

    @settings(**_SETTINGS)
    @given(a=finite_f32, b=finite_f32)
    def test_two_sum_error_free(a, b):
        """two_sum(a, b) = (s, e) with s + e == a + b EXACTLY and
        s == fl(a + b)."""
        s, e = dfl.two_sum(jnp.float32(a), jnp.float32(b))
        s, e = float(np.float32(s)), float(np.float32(e))
        assert Fraction(s) + Fraction(e) == Fraction(a) + Fraction(b)
        assert np.float32(s) == np.float32(a) + np.float32(b)

    # magnitudes bounded away from the subnormal range: Dekker's transform
    # is only error-free while no intermediate underflows/overflows
    _mag_f32 = st.floats(min_value=2.0 ** -30, max_value=2.0 ** 30,
                         width=32)

    @settings(**_SETTINGS)
    @given(am=_mag_f32, bm=_mag_f32, sa=st.booleans(), sb=st.booleans())
    def test_two_prod_error_free(am, bm, sa, sb):
        """two_prod(a, b) = (p, e) with p + e == a * b EXACTLY (Dekker/
        Veltkamp, no FMA)."""
        a = -am if sa else am
        b = -bm if sb else bm
        p, e = dfl.two_prod(jnp.float32(a), jnp.float32(b))
        p, e = float(np.float32(p)), float(np.float32(e))
        assert Fraction(p) + Fraction(e) == Fraction(a) * Fraction(b)

    def _exact_rne(v: Fraction) -> int:
        f = math.floor(v)
        r = v - f
        if r > Fraction(1, 2):
            return f + 1
        if r < Fraction(1, 2):
            return f
        return f if f % 2 == 0 else f + 1

    @settings(**_SETTINGS)
    @given(hi=finite_f32,
           rel=st.floats(min_value=-1.0, max_value=1.0, width=32),
           tie=st.booleans())
    def test_df_round_rne_exact(hi, rel, tie):
        """df_round_rne == round-half-even of the EXACT pair value —
        including adversarial exact-tie inputs (lo = +-1/2)."""
        hi32 = np.float32(hi)
        lo32 = (np.float32(0.5) if tie
                else np.float32(rel * abs(hi) * 2.0 ** -25))
        s, c, b = dfl.df_round_rne(dfl.DF(jnp.float32(hi32),
                                          jnp.float32(lo32)))
        got = int(np.float32(s)) + int(np.float32(c)) + int(np.float32(b))
        want = _exact_rne(Fraction(float(hi32)) + Fraction(float(lo32)))
        assert got == want

    @settings(**_SETTINGS)
    @given(hi=finite_f32,
           rel=st.floats(min_value=-1.0, max_value=1.0, width=32))
    def test_expansion3_digits_identity(hi, rel):
        """digit split reconstructs the rounded integer exactly, with every
        digit inside the uint32 reduction's |d| < 2^23 window."""
        hi32 = np.float32(hi)
        lo32 = np.float32(rel * abs(hi) * 2.0 ** -25)
        s, c, b = dfl.df_round_rne(dfl.DF(jnp.float32(hi32),
                                          jnp.float32(lo32)))
        d0, d1, d2 = dfl.expansion3_digits(s, c, b)
        d0, d1, d2 = (int(np.float32(x)) for x in (d0, d1, d2))
        assert d0 + d1 * 2 ** 22 + d2 * 2 ** 44 == \
            int(np.float32(s)) + int(np.float32(c)) + int(np.float32(b))
        assert all(abs(d) < 2 ** 23 for d in (d0, d1, d2))


# ---------------------------------------------------------------------------
# client-level bit-identity: df32 pipelines vs their f64 twins
# ---------------------------------------------------------------------------


def _pair_clients(params, pipeline):
    f64 = FHEClient(profile=params, pipeline=pipeline, datapath="f64")
    d32 = FHEClient(profile=params, pipeline=pipeline, datapath="df32")
    return f64, d32


@pytest.mark.parametrize("pipeline", ["staged", "megakernel"])
@pytest.mark.parametrize("logn,delta_bits,n_limbs,batch", [
    (5, 30, 2, 1),
    pytest.param(6, 40, 3, 3, marks=pytest.mark.slow),
    pytest.param(8, 45, 3, 2, marks=pytest.mark.slow),
])
def test_df32_bit_identical_to_f64_grid(pipeline, logn, delta_bits,
                                        n_limbs, batch):
    """Across the (N, Delta, L, B) grid, the df32 datapath round-trips
    BIT-identically to its f64 twin: same ciphertext words AND same
    decoded slot planes (every stage is exact; the pair collapse lands on
    the same f32 planes the f64 split produces on these grids)."""
    params = CKKSParams(logn=logn, n_limbs=n_limbs, delta_bits=delta_bits)
    f64, d32 = _pair_clients(params, pipeline)
    msgs = _msgs(f64.ctx, batch, seed=10 * logn + delta_bits)
    f64._nonce = d32._nonce = 50
    bf = f64.encode_encrypt_batch(msgs)
    bd = d32.encode_encrypt_batch(msgs)
    np.testing.assert_array_equal(np.asarray(bf.c0), np.asarray(bd.c0))
    np.testing.assert_array_equal(np.asarray(bf.c1), np.asarray(bd.c1))
    gf = f64.decrypt_decode_batch(bf.truncated(2))
    gd = d32.decrypt_decode_batch(bd.truncated(2))
    np.testing.assert_array_equal(gf, gd)
    assert encoder.boot_precision_bits(msgs, gd) >= 19.29


def test_default_client_is_megakernel_df32():
    """The device default flipped (ISSUE 5): a plain FHEClient now runs
    megakernel + df32; the host engine keeps staged + f64."""
    cl = FHEClient(profile="tiny")
    assert (cl.fourier, cl.pipeline, cl.datapath) == \
        ("device", "megakernel", "df32")
    host = FHEClient(profile="tiny", fourier="host")
    assert (host.pipeline, host.datapath) == ("staged", "f64")
    with pytest.raises(ValueError, match="datapath"):
        FHEClient(profile="tiny", datapath="fp55")
    with pytest.raises(ValueError, match="requires fourier='device'"):
        FHEClient(profile="tiny", fourier="host", datapath="df32")


# ---------------------------------------------------------------------------
# jaxpr scan: the default cores hold ZERO f64/u64-widening ops
# ---------------------------------------------------------------------------

_BAD_DTYPES = {"float64", "uint64", "int64", "complex128"}


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from _iter_eqns(sub)


def _subjaxprs(v):
    if isinstance(v, jax_core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jax_core.Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for item in v:
            yield from _subjaxprs(item)


def _is_wide(aval) -> bool:
    """A 64-bit-widening value: strong-typed f64/u64/i64/c128 data. Weak
    scalar int/float literals (Python ints plumbed as static ref indices,
    literal constants) canonicalize to 32-bit with JAX_ENABLE_X64=0 and
    never materialize 64-bit data, so they are not flagged."""
    dt = getattr(aval, "dtype", None)
    if dt is None or dt.name not in _BAD_DTYPES:
        return False
    weak_scalar = getattr(aval, "weak_type", False) and \
        getattr(aval, "ndim", 1) == 0
    return not weak_scalar


def _wide_dtypes(closed) -> set:
    found = set()
    jaxpr = closed.jaxpr
    for var in list(jaxpr.invars) + list(jaxpr.constvars):
        if _is_wide(var.aval):
            found.add(("input", var.aval.dtype.name))
    for eqn in _iter_eqns(jaxpr):
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(var, "aval", None)
            if aval is not None and _is_wide(aval):
                found.add((eqn.primitive.name, aval.dtype.name))
    return found


@pytest.mark.x64smoke
def test_default_cores_trace_x64_free(tiny_mega_client):
    """jaxpr scan of the jitted default (megakernel + df32) client cores:
    no float64, uint64, int64 or complex128 appears in ANY equation — the
    program traces identically with JAX_ENABLE_X64 disabled and lowers on
    f32/u32-only TPU VPUs."""
    client = tiny_mega_client
    ctx = client.ctx
    msgs = _msgs(ctx, 2, seed=9)
    ops = client.encrypt_operands(msgs)
    enc = jax.make_jaxpr(client.encrypt_impl)(*ops, jnp.uint32(0))
    assert _wide_dtypes(enc) == set(), \
        f"encrypt core is not x64-free: {_wide_dtypes(enc)}"

    c0 = jnp.zeros((2, 2, ctx.params.n), jnp.uint32)
    dec = jax.make_jaxpr(client.decrypt_impl)(
        c0, c0, jnp.float32(ctx.params.delta))
    assert _wide_dtypes(dec) == set(), \
        f"decrypt core is not x64-free: {_wide_dtypes(dec)}"


def test_staged_df32_cores_trace_x64_free():
    """The staged df32 pipeline is x64-free too (FFT kernel + digit glue +
    u32 NTT kernel + fused kernels)."""
    client = FHEClient(profile="tiny", pipeline="staged", datapath="df32")
    ctx = client.ctx
    msgs = _msgs(ctx, 2, seed=11)
    enc = jax.make_jaxpr(client.encrypt_impl)(*client.encrypt_operands(msgs),
                                              jnp.uint32(0))
    assert _wide_dtypes(enc) == set()
    c0 = jnp.zeros((2, 2, ctx.params.n), jnp.uint32)
    dec = jax.make_jaxpr(client.decrypt_impl)(
        c0, c0, jnp.float32(ctx.params.delta))
    assert _wide_dtypes(dec) == set()


def test_jaxpr_scan_detects_f64(tiny_device_client):
    """Scanner sanity: the f64 ORACLE core must trip the scan (otherwise
    the zero-f64 assertions above prove nothing)."""
    client = tiny_device_client            # staged f64 oracle fixture
    ctx = client.ctx
    msgs = _msgs(ctx, 2, seed=12)
    re, im = jnp.asarray(msgs.real), jnp.asarray(msgs.imag)
    enc = jax.make_jaxpr(client._encrypt_core_dev_impl)(re, im,
                                                        jnp.uint32(0))
    assert any(dt == "float64" for _, dt in _wide_dtypes(enc))


# ---------------------------------------------------------------------------
# x64smoke: the JAX_ENABLE_X64=0 CI lane subset (works in both modes)
# ---------------------------------------------------------------------------


@pytest.fixture()
def smoke_client(tiny_mega_client):
    """The session megakernel+df32 client (= the constructor default).
    Warming its jit cache at bucket shapes is safe: the launch-count tests
    re-trace impls through jax.make_jaxpr, outside the jit cache."""
    assert (tiny_mega_client.pipeline, tiny_mega_client.datapath) == \
        ("megakernel", "df32")
    return tiny_mega_client


@pytest.mark.x64smoke
def test_roundtrip_default_client_within_budget(smoke_client):
    """Default-client round trip inside the paper's 19.29-bit budget —
    runs identically with x64 on (fast lane) and off (smoke lane)."""
    cl = smoke_client
    msgs = _msgs(cl.ctx, 2, seed=21)
    got = cl.decrypt_decode_batch(cl.encode_encrypt_batch(msgs).truncated(2))
    assert encoder.boot_precision_bits(msgs, got) >= 19.29


@pytest.mark.x64smoke
def test_service_bit_identity_default_client(smoke_client):
    """Service vs direct bit-identity under the new default (and under
    JAX_ENABLE_X64=0 in the CI smoke lane): bucketing, padding and the
    nonce contract survive the df32 datapath."""
    from repro.fhe_client.service import ClientService
    cl = smoke_client
    msgs = _msgs(cl.ctx, 3, seed=22)
    base = cl.nonce
    direct = cl.encode_encrypt_batch(msgs)
    ref = cl.decrypt_decode_batch(direct.truncated(2))
    cl.nonce = base
    svc = ClientService(client=cl, buckets=(2,))
    cts = svc.encrypt_many(msgs)
    np.testing.assert_array_equal(np.asarray(cts.c0), np.asarray(direct.c0))
    np.testing.assert_array_equal(np.asarray(cts.c1), np.asarray(direct.c1))
    np.testing.assert_array_equal(svc.decrypt_many(direct.truncated(2)), ref)


_X64_OFF_SCRIPT = r"""
import hashlib
import numpy as np
import jax
import repro
assert not jax.config.jax_enable_x64, "JAX_ENABLE_X64=0 must be honoured"
from repro.fhe_client.client import FHEClient
cl = FHEClient(profile="tiny")
assert (cl.pipeline, cl.datapath) == ("megakernel", "df32")
rng = np.random.default_rng(33)
n = cl.ctx.params.n_slots
msgs = (rng.standard_normal((2, n)) + 1j * rng.standard_normal((2, n))) * .5
cl._nonce = 17
b = cl.encode_encrypt_batch(msgs)
got = cl.decrypt_decode_batch(b.truncated(2))
assert np.max(np.abs(got - msgs)) < 2.0 ** -19.29
h = hashlib.sha256(np.asarray(b.c0).tobytes()
                   + np.asarray(b.c1).tobytes()).hexdigest()
print("X64OFF-OK", h)
"""


def test_x64_disabled_bit_identical_subprocess(smoke_client):
    """JAX_ENABLE_X64=0 in a subprocess: the package honours the env, the
    default client round-trips, and its ciphertexts hash IDENTICALLY to
    the x64-enabled client in this process — no hidden f64/u64 dependence
    anywhere between keygen and ciphertext."""
    env = dict(os.environ)
    env["JAX_ENABLE_X64"] = "0"
    env["PYTHONPATH"] = os.path.abspath("src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _X64_OFF_SCRIPT],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    sub_hash = proc.stdout.split("X64OFF-OK")[1].strip()

    cl = smoke_client
    msgs = _msgs(cl.ctx, 2, seed=33)
    cl._nonce = 17
    b = cl.encode_encrypt_batch(msgs)
    here = hashlib.sha256(np.asarray(b.c0).tobytes()
                          + np.asarray(b.c1).tobytes()).hexdigest()
    assert here == sub_hash, "x64-on vs x64-off ciphertexts diverged"
