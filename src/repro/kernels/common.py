"""Shared in-kernel building blocks for the Pallas TPU kernels.

Per-prime constants come in two flavours. In the per-limb kernels everything
is *static* (baked into the kernel closure): modulus, shift-add k-terms,
Montgomery constants, and the OTF twiddle-generator seeds. In the
limb-folded kernels and the client megakernels the same scalars are
stacked into one (L, K) uint32 table (``stacked_kernel_consts``) in SMEM,
read by limb row (the grid index) at column offsets the stage loops
compute from the stage index. Both mirror the ASIC, where these live in registers /
a 27 KB seed SRAM — the TPU analogue is compile-time constants or an SMEM
seed table + VMEM-regenerated vectors, never HBM traffic.

The helpers here are pure uint32 jnp code, so the *same functions* run

  * inside Pallas kernel bodies (VPU lanes on TPU, Python in interpret mode),
  * in the jnp reference path (tests oracle the kernels against them).
"""

from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import cache, modmul
from repro.core.modmul import MontgomeryConstants
from repro.core.ntt import NTTPlan


# ---------------------------------------------------------------------------
# Fourier engine: unified launch config + row-streaming grid surface
# ---------------------------------------------------------------------------
# The ASIC multiplexes ONE Fourier datapath between two transform modes
# (paper Fig. 3a); on TPU the analogue is one launch-configuration surface
# that both Pallas kernels share: the NTT butterfly kernel and the df32
# SpecialFFT kernel stream row blocks through the same grid shape, and
# ``ops.fourier`` dispatches on ``FourierConfig.mode`` (see DESIGN.md).


@dataclasses.dataclass(frozen=True)
class FourierConfig:
    """Launch configuration of the reconfigurable Fourier engine.

    mode:
      * ``'ntt'``  — modular negacyclic NTT over RNS limb stacks
        (limb-folded grid, OTF twiddle generation, uint32 datapath);
      * ``'fft'``  — df32 complex canonical-embedding SpecialFFT
        (rows grid, VMEM-resident packed twiddle table, f32-pair datapath);
      * ``'host'`` — complex128 numpy oracle (reference path, not a kernel).

    block_rows is the rows-per-grid-step block of the streaming kernels;
    interpret=None auto-selects interpret mode on CPU (ops.default_interpret).
    """

    mode: str = "fft"
    block_rows: int = 1
    interpret: bool | None = None


FOURIER_MODES = ("ntt", "fft", "host")

# The scale/RNS/CRT interior of the client chain comes in two dtype paths:
#   * 'f64'  — exact df64/fmod/uint64 arithmetic. The interpret-mode oracle
#     (and the historical PR 1-4 behaviour); unlowerable on TPU VPUs.
#   * 'df32' — exact df32^2 split-limb chains + uint32 modular arithmetic
#     (dfloat.df_round_rne / expansion3_digits, rns.digits_to_residue /
#     crt2_centered_u32). Compiles without float64/uint64; bit-identical
#     integers by construction (DESIGN.md §4).
DATAPATHS = ("f64", "df32")


def check_datapath(datapath: str) -> str:
    if datapath not in DATAPATHS:
        raise ValueError(f"datapath must be one of {DATAPATHS}, "
                         f"got {datapath!r}")
    return datapath


def stacked_digit_consts(q_list) -> tuple:
    """Per-limb Montgomery-form radix constants ((c22, c44), ...) for the
    df32 RNS digit reduction — the seed-table analogue for the digit stage
    (the megakernel reads them from an (L, 2) SMEM table by limb)."""
    from repro.core import rns
    return tuple(rns.digit_consts(int(q)) for q in q_list)


def row_grid(rows: int, block_rows: int) -> tuple[tuple[int, ...], int]:
    """Grid + clamped block size for a rows-streaming kernel.

    block_rows is clamped to ``rows`` and must divide it (falls back to 1).
    Shared by the NTT butterfly and df32 FFT kernels so both Fourier modes
    launch through the same grid arithmetic.
    """
    br = max(1, min(block_rows, rows))
    if rows % br:
        br = 1
    return (rows // br,), br


def row_block_spec(block_rows: int, n: int) -> pl.BlockSpec:
    """(block_rows, N) VMEM block indexed by the rows grid axis."""
    return pl.BlockSpec((block_rows, n), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)


@dataclasses.dataclass(frozen=True)
class PlanConsts:
    """Static per-(prime, N) constants for in-kernel NTT/INTT.

    ``fwd_factors[s]`` are the doubling factors (Montgomery form) that expand
    stage s's twiddles from its seed: A_{k+1} = [A_k, A_k * f_k]. Exactly the
    paper's unified OTF TF Gen seed+step state, ~log^2(N) scalars per prime.
    """

    q: int
    n: int
    logn: int
    mont: MontgomeryConstants
    fwd_base_mont: tuple[int, ...]          # per-stage seed, Montgomery form
    fwd_factors: tuple[tuple[int, ...], ...]  # per-stage doubling factors
    inv_base_mont: tuple[int, ...]
    inv_factors: tuple[tuple[int, ...], ...]
    n_inv_mont: int
    psi: int
    psi_inv: int
    r_mod_q: int                            # R mod q = Montgomery form of 1

    def seed_scalar_count(self) -> int:
        return (len(self.fwd_base_mont) + len(self.inv_base_mont)
                + sum(len(f) for f in self.fwd_factors)
                + sum(len(f) for f in self.inv_factors) + 2)


_PLAN_CONSTS_MEMO = cache.LRUCache(capacity=256, name="plan_consts")


def plan_consts(plan: NTTPlan) -> PlanConsts:
    """Memoised by plan CONTENT (``cache.plan_key``: (q, N) determines
    every derived constant), LRU-bounded.

    This used to be keyed by ``id(plan)`` without retaining the plan —
    once plans can actually be garbage-collected (bounded ``make_plan`` /
    context caches under the multi-tenant registry, ISSUE 8), CPython id
    reuse let a dead plan's entry answer for a NEW plan with a different
    prime: stale NTT constants, silently wrong ciphertexts. Pinned by
    tests/test_multi_tenant.py::test_plan_consts_survives_gc_id_reuse."""
    key = cache.plan_key(plan)
    cached = _PLAN_CONSTS_MEMO.get(key)
    if cached is not None:
        return cached
    q = plan.prime.q
    n = plan.n
    logn = n.bit_length() - 1
    r = (1 << 32) % q
    s = plan.seeds

    def factors(step: int, m: int) -> tuple[int, ...]:
        # step^(m/2), step^(m/4), ..., step^1  (Montgomery form)
        out = []
        e = m // 2
        while e >= 1:
            out.append((pow(step, e, q) * r) % q)
            e //= 2
        return tuple(out)

    fwd_base, fwd_f, inv_base, inv_f = [], [], [], []
    for st in range(logn):
        m = 1 << st                       # forward CT stage: m twiddles
        fwd_base.append((s.fwd_base[st] * r) % q)
        fwd_f.append(factors(s.fwd_step[st], m))
    for st in range(logn):                # inverse GS stage: h = n >> (st+1)
        h = n >> (st + 1)
        inv_base.append((s.inv_base[st] * r) % q)
        inv_f.append(factors(s.inv_step[st], h))

    psi_inv = pow(plan.psi, -1, q)
    pc = PlanConsts(
        q=q, n=n, logn=logn, mont=plan.mont,
        fwd_base_mont=tuple(fwd_base), fwd_factors=tuple(fwd_f),
        inv_base_mont=tuple(inv_base), inv_factors=tuple(inv_f),
        n_inv_mont=plan.n_inv_mont, psi=plan.psi, psi_inv=psi_inv,
        r_mod_q=r,
    )
    _PLAN_CONSTS_MEMO.put(key, pc)
    return pc


# ---------------------------------------------------------------------------
# Stacked per-limb constants for limb-folded kernels (grid = (L, ...))
# ---------------------------------------------------------------------------
# Folding the limb loop into the Pallas grid means per-limb constants can no
# longer be Python-closure scalars: they arrive as one (L, K) uint32 array,
# read by limb row, each scalar at a column offset that depends only on the
# stage (``_fwd_off``/``_inv_off`` compute it from a traced stage index).
# Layout per limb row:
#
#   [0] q   [1] -q^{-1} mod 2^32   [2] N^{-1} (Montgomery form)
#   then per forward stage s = 0..logn-1:  base_s, f_0..f_{s-1}
#   then per inverse stage t = 0..logn-1:  base_t, f_0..f_{logn-2-t}
#
# This is the array-of-seeds analogue of the paper's 27 KB seed SRAM: one
# row of OTF TF Gen state per prime, streamed to the grid step that owns
# that limb.

OFF_Q = 0
OFF_QINV = 1
OFF_NINV = 2
_OFF_STAGES = 3


@dataclasses.dataclass(frozen=True)
class StackedKernelConsts:
    """(L, K) uint32 table of per-limb kernel constants + column offsets."""

    n: int
    logn: int
    n_limbs: int
    fwd_off: tuple[int, ...]     # column of stage-s [base, factors...]
    inv_off: tuple[int, ...]
    n_scalars: int
    table: np.ndarray            # (L, n_scalars) uint32

    def fwd_nfac(self, s: int) -> int:
        return s                                  # m = 2^s -> log2(m) factors

    def inv_nfac(self, st: int) -> int:
        return self.logn - 1 - st                 # h = N >> (st+1)


_STACKED_KC_MEMO = cache.LRUCache(capacity=64, name="stacked_kernel_consts")


def stacked_kernel_consts(plans) -> StackedKernelConsts:
    """Stack ``plan_consts`` of several same-N plans into one (L, K) table.
    Memoised by plan content (per-limb (q, N) keys — see ``plan_consts``
    for why identity keys are unsound), LRU-bounded."""
    key = cache.plans_key(plans)
    cached = _STACKED_KC_MEMO.get(key)
    if cached is not None:
        return cached
    pcs = [plan_consts(p) for p in plans]
    n, logn = pcs[0].n, pcs[0].logn
    assert all(pc.n == n for pc in pcs)

    fwd_off, inv_off = [], []
    cur = _OFF_STAGES
    for s in range(logn):
        fwd_off.append(cur)
        cur += 1 + s
    for st in range(logn):
        inv_off.append(cur)
        cur += 1 + (logn - 1 - st)

    table = np.zeros((len(pcs), cur), np.uint32)
    for i, pc in enumerate(pcs):
        table[i, OFF_Q] = pc.q
        table[i, OFF_QINV] = pc.mont.qinv_neg
        table[i, OFF_NINV] = pc.n_inv_mont
        for s in range(logn):
            o = fwd_off[s]
            table[i, o] = pc.fwd_base_mont[s]
            table[i, o + 1:o + 1 + s] = pc.fwd_factors[s]
        for st in range(logn):
            o = inv_off[st]
            nf = logn - 1 - st
            table[i, o] = pc.inv_base_mont[st]
            table[i, o + 1:o + 1 + nf] = pc.inv_factors[st]

    kc = StackedKernelConsts(
        n=n, logn=logn, n_limbs=len(pcs),
        fwd_off=tuple(fwd_off), inv_off=tuple(inv_off),
        n_scalars=cur, table=table,
    )
    _STACKED_KC_MEMO.put(key, kc)
    return kc


# ---------------------------------------------------------------------------
# In-kernel OTF twiddle generation (the unified OTF TF Gen)
# ---------------------------------------------------------------------------


def gen_twiddles(base_mont: int, factor_list: tuple[int, ...],
                 pc: PlanConsts) -> jnp.ndarray:
    """[base * step^bitrev_m(i)]_{i<m}, Montgomery form, by log2(m) doublings.

    Runs entirely in VMEM: each doubling is one vector shift-add Montgomery
    multiply by a scalar constant. Zero HBM reads.
    """
    # broadcasted_iota keeps `a` a traced value inside Pallas kernels
    # (a jnp.full here would be a captured constant, which Pallas rejects).
    zero = jax.lax.broadcasted_iota(jnp.uint32, (1,), 0)
    a = zero + np.uint32(base_mont)
    for f in factor_list:
        prod = modmul.mulmod_montgomery_sa_limb(a, np.uint32(f), pc.mont)
        a = jnp.concatenate([a, prod])
    return a


def gen_geometric(base_mont: int, ratio: int, length: int,
                  pc: PlanConsts) -> jnp.ndarray:
    """[base * ratio^i]_{i<length} (Montgomery form), by doubling.
    Used for psi^n pre/post-twist vectors in the four-step path."""
    q = pc.q
    r = pc.r_mod_q
    zero = jax.lax.broadcasted_iota(jnp.uint32, (1,), 0)
    a = zero + np.uint32(base_mont)
    while a.shape[0] < length:
        f = (pow(ratio % q, a.shape[0], q) * r) % q
        prod = modmul.mulmod_montgomery_sa_limb(a, np.uint32(f), pc.mont)
        a = jnp.concatenate([a, prod])
    return a[:length]


# ---------------------------------------------------------------------------
# In-kernel NTT/INTT stage loops (shared by butterfly + fused client kernels)
# ---------------------------------------------------------------------------


def ntt_stages(x: jnp.ndarray, pc: PlanConsts) -> jnp.ndarray:
    """Forward negacyclic NTT on (rows, N) uint32, merged-psi CT DIT.
    In-order input -> bit-reversed output. Twiddles OTF-generated per stage."""
    q, c, n = pc.q, pc.mont, pc.n
    rows = x.shape[0]
    m, t = 1, n
    while m < n:
        t //= 2
        tw = gen_twiddles(pc.fwd_base_mont[_s(m)], pc.fwd_factors[_s(m)], pc)
        x = x.reshape(rows, m, 2, t)
        u = x[:, :, 0, :]
        v = modmul.mulmod_montgomery_sa_limb(x[:, :, 1, :], tw[None, :, None], c)
        x = jnp.stack(
            [modmul.addmod(u, v, q), modmul.submod(u, v, q)], axis=2
        ).reshape(rows, n)
        m *= 2
    return x


def intt_stages(x: jnp.ndarray, pc: PlanConsts) -> jnp.ndarray:
    """Inverse negacyclic NTT on (rows, N): bit-reversed input -> in-order
    output, N^-1 folded in at the end."""
    q, c, n = pc.q, pc.mont, pc.n
    rows = x.shape[0]
    h, t = n // 2, 1
    s = 0
    while h >= 1:
        tw = gen_twiddles(pc.inv_base_mont[s], pc.inv_factors[s], pc)
        x = x.reshape(rows, h, 2, t)
        u, v = x[:, :, 0, :], x[:, :, 1, :]
        even = modmul.addmod(u, v, q)
        odd = modmul.mulmod_montgomery_sa_limb(
            modmul.submod(u, v, q), tw[None, :, None], c)
        x = jnp.concatenate([even, odd], axis=-1).reshape(rows, h * 2 * t)
        t *= 2
        h //= 2
        s += 1
    x = x.reshape(rows, n)
    return modmul.mulmod_montgomery_sa_limb(x, np.uint32(pc.n_inv_mont), c)


def _s(m: int) -> int:
    return m.bit_length() - 1


# ---------------------------------------------------------------------------
# Traced-constant variants: per-limb scalars read from the stacked-constants
# ref (limb-folded grid kernels and the streaming megakernels)
# ---------------------------------------------------------------------------
# REDC with traced (q, -q^-1) uses the general 16-bit-limb multiply path
# (modmul.mulmod_montgomery_limb_t) because shift-add k-term exponents are
# structurally per-prime and cannot be traced; outputs are bit-identical
# (see the modmul docstring), so the folded kernels match the per-limb
# shift-add kernels word-for-word.
#
# Tiled layout. On the chip a length-n vector lives in VMEM as (8, 128)
# tiles, so the traced stage loops work on a 2-D view (R, C) of every
# polynomial (``tile_layout``): C = min(N/2, 128) lanes, R = N / C rows,
# element i at (i // C, i % C). A butterfly stage with pair stride t then
# never reshapes: its partner is one ``pltpu.roll`` away, along the lanes
# for t < C and along the rows for t >= C, and a select on bit t of the
# index picks the butterfly half. Every element computes its own output,
# so each product is formed twice; the values are the ones the reshape
# formulation computes, word for word.

LANES = 128

def tile_layout(n: int) -> tuple[int, int]:
    """(R, C) view of a length-n ring polynomial: C = min(n/2, 128) lanes.
    The slot vectors (length n/2) of the same ring share C, so the
    re ++ im coefficient concatenation is a row-axis concat."""
    c = min(max(n // 2, 1), LANES)
    return n // c, c


def butterfly_pairs(x, shift, axis: int):
    """Split a tiled (..., R, C) array into the (u, v, upper) views of the
    butterfly stage whose pair stride is `shift` along `axis` (the lane
    axis for strides < C, the row axis in units of rows otherwise): u/v
    are the lower/upper pair member seen from every element, upper is
    True where the element is the upper member. `shift` may be traced."""
    size = x.shape[axis]
    shape = [1] * x.ndim
    shape[axis] = size
    idx = jax.lax.broadcasted_iota(jnp.int32, tuple(shape), axis)
    upper = (idx & shift) != 0
    partner = jnp.where(upper, pltpu.roll(x, shift, axis),
                        pltpu.roll(x, size - shift, axis))
    return (jnp.where(upper, partner, x), jnp.where(upper, x, partner),
            upper)


def stage_loops(x, n_stages: int, first_row_stages: bool, stage):
    """Run ``stage(s, x, axis, shift)`` for s in [0, n_stages) as two
    ``fori_loop``s over a tiled (..., R, C) array (or pytree of them): the stages whose pair
    stride crosses rows, then those inside a row (or the other way round).
    Strides halve from R*C/2 down to 1 when `first_row_stages`, and double
    from 1 up otherwise. A loop keeps the traced body one stage long (the
    chip compiles it once) and makes XLA's CPU backend materialize every
    stage in interpret mode instead of fusing the whole pipeline into one
    recomputing expression."""
    leaf = jax.tree_util.tree_leaves(x)[0]
    n_rows, n_cols = leaf.shape[-2:]
    row_axis, lane_axis = leaf.ndim - 2, leaf.ndim - 1
    log_r = n_rows.bit_length() - 1
    log_c = n_cols.bit_length() - 1
    assert log_r + log_c == n_stages
    one = jnp.int32(1)

    def loop(x, lo, hi, axis, shift_of):
        if hi <= lo:
            return x
        # int32 bounds: the index must not widen to int64 under x64
        return jax.lax.fori_loop(
            jnp.int32(lo), jnp.int32(hi),
            lambda s, y: stage(s, y, axis, shift_of(s)), x)

    if first_row_stages:
        x = loop(x, 0, log_r, row_axis,
                 lambda s: jnp.right_shift(jnp.int32(n_rows), s + one))
        return loop(x, log_r, n_stages, lane_axis,
                    lambda s: jnp.right_shift(jnp.int32(n_cols),
                                              s - log_r + one))
    x = loop(x, 0, log_c, lane_axis, lambda s: jnp.left_shift(one, s))
    return loop(x, log_c, n_stages, row_axis,
                lambda s: jnp.left_shift(one, s - log_c))


def _mont_one(q):
    """R mod q (the Montgomery form of 1) from a traced q in (2^30, 2^31):
    2^32 - q < 3q, so two conditional subtractions reduce it."""
    one = jnp.zeros((1, 1), jnp.uint32) - q
    for _ in range(2):
        one = jnp.where(one >= q, one - q, one)
    return one


def expand_twiddles_t(c_ref, row, off, bit0, shape: tuple[int, int],
                      q, qinv_neg):
    """OTF twiddles of one stage, expanded to the tiled (R, C) layout.

    The doubling A_{k+1} = [A_k, A_k * f_k] gives element j of a stage's
    twiddle vector as base * prod_{bit k of j set} f_k; in the expanded
    view element i reads j = i >> bit0. The lane bits of i build a (1, C)
    factor and the row bits an (R, 1) factor, each by select-multiplies
    with the seed scalars at column `off` of c_ref row `row`, and one
    Montgomery product joins them. `row`, `off` and `bit0` may be traced
    (the stage loops); only the seeds are ever read — the paper's unified
    OTF TF Gen, in tile shape."""
    n_rows, n_cols = shape
    lane_bits = n_cols.bit_length() - 1
    logn = lane_bits + n_rows.bit_length() - 1
    lane_i = jax.lax.broadcasted_iota(jnp.int32, (1, n_cols), 1)
    row_i = jax.lax.broadcasted_iota(jnp.int32, (n_rows, 1), 0)
    lane_v = jnp.zeros((1, n_cols), jnp.uint32) + c_ref[row, off]
    row_v = jnp.zeros((n_rows, 1), jnp.uint32) + _mont_one(q)
    for b in range(logn):
        k = b - bit0                      # factor index; < 0: bit unused
        f = c_ref[row, off + 1 + jnp.maximum(k, 0)]
        if b < lane_bits:
            use = (((lane_i >> b) & 1) != 0) & (k >= 0)
            lane_v = jnp.where(
                use, modmul.mulmod_montgomery_limb_t(lane_v, f, q, qinv_neg),
                lane_v)
        else:
            use = (((row_i >> (b - lane_bits)) & 1) != 0) & (k >= 0)
            row_v = jnp.where(
                use, modmul.mulmod_montgomery_limb_t(row_v, f, q, qinv_neg),
                row_v)
    return modmul.mulmod_montgomery_limb_t(
        jnp.broadcast_to(lane_v, shape), jnp.broadcast_to(row_v, shape),
        q, qinv_neg)


def _fwd_off(s):
    """Column of forward stage s in the stacked table (traced s)."""
    return _OFF_STAGES + ((s * (s + 1)) >> 1)


def _inv_off(st, logn: int):
    """Column of inverse stage st in the stacked table (traced st)."""
    return (_OFF_STAGES + logn * (logn + 1) // 2 + st * logn
            - ((st * (st - 1)) >> 1))


def ntt_tiled_t(x: jnp.ndarray, c_ref, kc: StackedKernelConsts, q, qinv_neg,
                row=0) -> jnp.ndarray:
    """Forward negacyclic NTT (merged-psi CT DIT, in-order in ->
    bit-reversed out) of tiled (rows, R, C) uint32 polynomials, per-limb
    scalars from c_ref row `row` (a static int or a traced grid index).
    Stage s has pair stride N >> (s+1) and its twiddle index starts at
    bit log2(N) - s."""
    shape = x.shape[-2:]
    assert kc.fwd_off == tuple(_fwd_off(s) for s in range(kc.logn))

    def stage(s, x, axis, shift):
        tw = expand_twiddles_t(c_ref, row, _fwd_off(s), kc.logn - s, shape,
                               q, qinv_neg)
        u, v, upper = butterfly_pairs(x, shift, axis)
        vw = modmul.mulmod_montgomery_limb_t(v, tw, q, qinv_neg)
        return jnp.where(upper, modmul.submod(u, vw, q),
                         modmul.addmod(u, vw, q))

    return stage_loops(x, kc.logn, True, stage)


def intt_tiled_t(x: jnp.ndarray, c_ref, kc: StackedKernelConsts, q, qinv_neg,
                 row=0) -> jnp.ndarray:
    """Inverse negacyclic NTT (GS DIF, bit-reversed in -> in-order out) of
    tiled (rows, R, C) polynomials, N^-1 folded in at the end. Stage st
    has pair stride 2^st and its twiddle index starts at bit st + 1."""
    shape = x.shape[-2:]
    assert kc.inv_off == tuple(_inv_off(s, kc.logn) for s in range(kc.logn))

    def stage(st, x, axis, shift):
        tw = expand_twiddles_t(c_ref, row, _inv_off(st, kc.logn), st + 1,
                               shape, q, qinv_neg)
        u, v, upper = butterfly_pairs(x, shift, axis)
        odd = modmul.mulmod_montgomery_limb_t(modmul.submod(u, v, q), tw, q,
                                              qinv_neg)
        return jnp.where(upper, odd, modmul.addmod(u, v, q))

    x = stage_loops(x, kc.logn, False, stage)
    return modmul.mulmod_montgomery_limb_t(x, c_ref[row, OFF_NINV], q,
                                           qinv_neg)


def _on_tiles(f, x, n: int):
    """Run a tiled stage loop on tiled (rows, R, C) polynomials as they
    are, or on (rows, N) ones (the staged and server kernels' block
    layout) through the tiled view."""
    if x.ndim == 3:
        return f(x)
    rows = x.shape[0]
    return f(x.reshape((rows,) + tile_layout(n))).reshape(rows, n)


def ntt_stages_t(x: jnp.ndarray, c_ref, kc: StackedKernelConsts,
                 q, qinv_neg, row=0) -> jnp.ndarray:
    """Forward negacyclic NTT on (rows, N) or tiled (rows, R, C) uint32
    with traced per-limb constants (``ntt_tiled_t``)."""
    return _on_tiles(
        lambda t: ntt_tiled_t(t, c_ref, kc, q, qinv_neg, row), x, kc.n)


def intt_stages_t(x: jnp.ndarray, c_ref, kc: StackedKernelConsts,
                  q, qinv_neg, row=0) -> jnp.ndarray:
    """Inverse negacyclic NTT on (rows, N) or tiled (rows, R, C) with
    traced per-limb constants, N^-1 folded in (``intt_tiled_t``)."""
    return _on_tiles(
        lambda t: intt_tiled_t(t, c_ref, kc, q, qinv_neg, row), x, kc.n)


# ---------------------------------------------------------------------------
# Balanced base-256 digit decomposition (int8 MXU feeding, four-step path)
# ---------------------------------------------------------------------------

N_DIGITS = 4


def balanced_digits_jnp(v: jnp.ndarray) -> list[jnp.ndarray]:
    """uint32 (< 2^31, residues of ~30-bit q) -> 4 int8 balanced digits with
    v == sum d_i * 256^i. Digit products then fit the int8 MXU exactly."""
    digs = []
    x = v
    for _ in range(N_DIGITS):
        d = x & np.uint32(255)
        over = d >= np.uint32(128)
        d_signed = jnp.where(over, d.astype(jnp.int32) - 256,
                             d.astype(jnp.int32))
        x = (x >> 8) + over.astype(jnp.uint32)
        digs.append(d_signed.astype(jnp.int8))
    return digs


def balanced_digits_np(v: np.ndarray) -> np.ndarray:
    """Host-side digit decomposition for the precomputed F matrices.
    Returns (4, *v.shape) int8."""
    out = np.zeros((N_DIGITS,) + v.shape, dtype=np.int8)
    x = v.astype(np.int64)
    for i in range(N_DIGITS):
        d = x & 255
        over = d >= 128
        out[i] = np.where(over, d - 256, d).astype(np.int8)
        x = (x >> 8) + over.astype(np.int64)
    assert np.all(x == 0), "value exceeded 4 balanced digits"
    return out


def recombine_digit_matmuls(partials, pc: PlanConsts) -> jnp.ndarray:
    """Combine int32 digit-product matmul results into residues mod q.

    partials: dict {(i, j): S_ij} with S_ij = A_i @ B_j (int32, |S| < 2^22).
    Result = sum_ij S_ij * 2^(8(i+j)) mod q. Grouped by g = i+j (7 groups,
    |group sum| < 2^24), then one Barrett multiply by 2^(8g) mod q per group.
    """
    q = pc.q
    qc = pc.mont
    groups: dict[int, jnp.ndarray] = {}
    for (i, j), s in partials.items():
        g = i + j
        groups[g] = s if g not in groups else groups[g] + s
    acc = None
    for g, sg in groups.items():
        # shift into [0, q + 2^24): sg in (-2^24, 2^24), q ~ 2^30
        u = (sg + np.int32(q)).astype(jnp.uint32)
        cg = np.uint32(pow(2, 8 * g, q))
        r = modmul.mulmod_barrett_limb(u, cg, qc)
        acc = r if acc is None else modmul.addmod(acc, r, q)
    return acc
