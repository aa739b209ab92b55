"""Fused streaming encrypt/decrypt Pallas kernels — the RSC datapath.

This is the paper's streaming architecture end-to-end in ONE kernel per limb:

  encrypt:  Philox PRNG (v, e0, e1)  ->  negacyclic NTT (OTF twiddles)
            ->  c0 = v*b + e0 + pt,  c1 = v*a + e1          (one VMEM pass)
  decrypt:  m_ntt = c0 + c1*s  ->  INTT  ->  coefficient residues

HBM traffic per ciphertext limb is exactly: read pt (+ pk limbs), write
c0/c1 — masks, errors and twiddles are generated on-chip (in VMEM) from the
128-bit seed and the twiddle seed scalars, reproducing the paper's
ABC-FHE_All configuration (Fig. 6b). The Philox streams match the host-side
``repro.core.prng`` bit-for-bit, so fused ciphertexts decrypt with the
reference path and vice versa.

Two launch shapes are provided:

  * per-limb (``encrypt_limb``/``decrypt_limb``): grid = (batch,), per-limb
    constants baked statically into the kernel closure — the reference
    oracle, one pallas_call per limb;
  * limb-folded (``encrypt_limbs``/``decrypt_limbs``): grid = (L, batch),
    per-limb constants (q, -q^-1, OTF twiddle seed/step scalars, N^-1)
    streamed from a stacked (L, K) SMEM table — ONE pallas_call for the
    whole (B, L, N) batch, the hot path of the batched client pipeline.

Both are bit-identical (the folded REDC uses traced general multiplies in
place of static shift-add k-terms; see ``modmul.mulmod_montgomery_limb_t``).
Limbs remain independent until CRT, so multi-device sharding can still
split the leading grid axis.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import modmul, prng
from repro.core.context import CKKSContext
from repro.core.encryptor import (
    STREAM_ENC_E0, STREAM_ENC_E1, STREAM_ENC_V,
)
from repro.kernels import common


# ---------------------------------------------------------------------------
# Kernel-safe Philox samplers (2D iota, traced stream scalar)
# ---------------------------------------------------------------------------


def _elem_index(shape):
    """uint32 coefficient index of every element of a (rows, n) block or
    a tiled (rows, R, C) block (element (r, c) of a tile holds r*C + c)."""
    if len(shape) == 2:
        return jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    return (jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
            * np.uint32(shape[2])
            + jax.lax.broadcasted_iota(jnp.uint32, shape, 2))


def _random_u32_k(seed128: int, stream, shape, word: int):
    """uint32 Philox draw over a (rows, n) or tiled (rows, R, C) block;
    `stream` may be a traced scalar (one stream for every row) or a
    traced column broadcasting over the row axis (one stream per row, the
    batch-blocked kernels).

    Bit-identical per row to ``prng.random_u32`` (same counter layout), but
    built from numpy-literal key material and an iota so Pallas captures
    nothing.
    """
    parts = [np.uint32((seed128 >> (32 * i)) & 0xFFFFFFFF) for i in range(4)]
    key = (parts[0], parts[1])
    idx = _elem_index(shape)
    z = jnp.zeros_like(idx)
    ctr = (
        idx,
        z + jnp.asarray(stream, jnp.uint32),
        z + (np.uint32(word) ^ parts[2]),
        z + parts[3],
    )
    return prng.philox_4x32(ctr, key)[0]


def _zo_k(seed128: int, stream, shape):
    u = _random_u32_k(seed128, stream, shape, 0)
    return jnp.where(
        u < np.uint32(1 << 30), jnp.int32(1),
        jnp.where(u < np.uint32(1 << 31), jnp.int32(-1), jnp.int32(0)))


def _cbd_k(seed128: int, stream, shape):
    a = _random_u32_k(seed128, stream, shape, 0)
    b = _random_u32_k(seed128, stream, shape, 1)
    return (prng._popcount21(a).astype(jnp.int32)
            - prng._popcount21(b).astype(jnp.int32))


def _to_residue_k(x, q):
    """Signed int32 in (-q, q) -> uint32 residue, no 64-bit ops. `q` may be
    a Python int or a traced uint32 scalar (limb-folded kernels)."""
    qi = np.int32(q) if isinstance(q, (int, np.integer)) else q.astype(jnp.int32)
    return jnp.where(x < 0, x + qi, x).astype(jnp.uint32)


# ---------------------------------------------------------------------------
# Encrypt kernel (per limb): PRNG -> NTT -> pointwise
# ---------------------------------------------------------------------------


def _encrypt_kernel(pt_ref, b_ref, a_ref, c0_ref, c1_ref, *,
                    pc: common.PlanConsts, seed: int, nonce0: int):
    n, q, c = pc.n, pc.q, pc.mont
    nonce = pl.program_id(0).astype(jnp.uint32) + np.uint32(nonce0)
    sv = np.uint32(STREAM_ENC_V) + np.uint32(16) * nonce
    s0 = np.uint32(STREAM_ENC_E0) + np.uint32(16) * nonce
    s1 = np.uint32(STREAM_ENC_E1) + np.uint32(16) * nonce

    v = _to_residue_k(_zo_k(seed, sv, (1, n)), q)
    e0 = _to_residue_k(_cbd_k(seed, s0, (1, n)), q)
    e1 = _to_residue_k(_cbd_k(seed, s1, (1, n)), q)

    v_h = common.ntt_stages(v, pc)
    e0_h = common.ntt_stages(e0, pc)
    e1_h = common.ntt_stages(e1, pc)

    vb = modmul.mulmod_montgomery_sa_limb(v_h, b_ref[...], c)
    va = modmul.mulmod_montgomery_sa_limb(v_h, a_ref[...], c)
    c0_ref[...] = modmul.addmod(
        modmul.addmod(vb, e0_h, q), pt_ref[...], q)
    c1_ref[...] = modmul.addmod(va, e1_h, q)


def encrypt_limb(pt_l, b_mont_l, a_mont_l, ctx: CKKSContext, limb: int,
                 seed: int, nonce0: int = 0, interpret: bool = True):
    """Fused encrypt of one limb. pt_l: (batch, N) uint32; pk rows (N,)."""
    pc = common.plan_consts(ctx.plans[limb])
    batch, n = pt_l.shape
    dspec = pl.BlockSpec((1, n), lambda i: (i, 0), memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, n), lambda i: (0, 0), memory_space=pltpu.VMEM)
    shape = jax.ShapeDtypeStruct((batch, n), jnp.uint32)
    call = pl.pallas_call(
        functools.partial(_encrypt_kernel, pc=pc, seed=seed, nonce0=nonce0),
        grid=(batch,),
        in_specs=[dspec, kspec, kspec],
        out_specs=(dspec, dspec),
        out_shape=(shape, shape),
        interpret=interpret,
    )
    return call(pt_l, b_mont_l.reshape(1, n), a_mont_l.reshape(1, n))


# ---------------------------------------------------------------------------
# Decrypt kernel (per limb): pointwise -> INTT
# ---------------------------------------------------------------------------


def _decrypt_kernel(c0_ref, c1_ref, s_ref, m_ref, *, pc: common.PlanConsts):
    q, c = pc.q, pc.mont
    c1s = modmul.mulmod_montgomery_sa_limb(c1_ref[...], s_ref[...], c)
    m_ntt = modmul.addmod(c0_ref[...], c1s, q)
    m_ref[...] = common.intt_stages(m_ntt, pc)


def decrypt_limb(c0_l, c1_l, s_mont_l, ctx: CKKSContext, limb: int,
                 interpret: bool = True):
    """Fused decrypt of one limb -> coefficient-domain residues (batch, N)."""
    pc = common.plan_consts(ctx.plans[limb])
    batch, n = c0_l.shape
    dspec = pl.BlockSpec((1, n), lambda i: (i, 0), memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, n), lambda i: (0, 0), memory_space=pltpu.VMEM)
    call = pl.pallas_call(
        functools.partial(_decrypt_kernel, pc=pc),
        grid=(batch,),
        in_specs=[dspec, dspec, kspec],
        out_specs=dspec,
        out_shape=jax.ShapeDtypeStruct((batch, n), jnp.uint32),
        interpret=interpret,
    )
    return call(c0_l, c1_l, s_mont_l.reshape(1, n))


# ---------------------------------------------------------------------------
# Limb-folded fused kernels: grid = (L, B/bb), ONE pallas_call per batch
# ---------------------------------------------------------------------------
# The per-limb launches above are kept as the reference oracle; the folded
# variants below stream the per-limb constants from a (L, K) SMEM table
# (common.stacked_kernel_consts) and the nonce base from a (1, 1) SMEM
# scalar, so one launch covers the whole (B, L, N) batch. Each grid step
# owns a (bb, N) *block* of batch rows (default: the whole batch), running
# the PRNG, NTT stages and pointwise algebra vectorized across rows — the
# batching win on top of the launch-count win. Philox streams depend only
# on (seed, nonce = nonce0 + batch_idx), never on the limb, so row r of
# block b regenerates exactly the randomness the reference path samples
# for ciphertext b*bb + r — bit-identical outputs.


def sample_vee_k(seed: int, nonce, shape):
    """In-kernel (v, e0, e1) encryption randomness for a block of batch
    rows, (rows, n) or tiled (rows, R, C).

    nonce: traced uint32 column (base + per-row offset) broadcasting over
    the row axis. Returns SIGNED int32 draws — limb-independent, exactly
    the streams the host reference samples — so one sampling pass feeds
    every limb (the residue cast is per-limb).
    """
    sv = np.uint32(STREAM_ENC_V) + np.uint32(16) * nonce
    s0 = np.uint32(STREAM_ENC_E0) + np.uint32(16) * nonce
    s1 = np.uint32(STREAM_ENC_E1) + np.uint32(16) * nonce
    return (_zo_k(seed, sv, shape), _cbd_k(seed, s0, shape),
            _cbd_k(seed, s1, shape))


def encrypt_limb_stage(vee, pt_l, b_l, a_l, c_ref,
                       kc: common.StackedKernelConsts, limb: int = 0):
    """One limb of the streaming encrypt datapath: signed (v, e0, e1) ->
    residues -> NTT -> pointwise with the public key rows.

    vee: signed int32 (rows, N) draws from ``sample_vee_k``; pt_l/b_l/a_l:
    this limb's NTT-domain plaintext block and Montgomery-form pk rows;
    c_ref: the stacked-constants ref, indexed at row `limb` (0 for the
    limb-folded kernels whose block is one row). Returns (c0_l, c1_l)
    uint32 (rows, N).
    """
    q = c_ref[limb, common.OFF_Q]
    qinv = c_ref[limb, common.OFF_QINV]
    v, e0, e1 = (_to_residue_k(x, q) for x in vee)

    # one stacked stage loop for all three polynomials: the NTT is
    # row-independent, so this is bit-identical to three separate
    # transforms while tracing a third of the butterfly ops
    h = common.ntt_stages_t(jnp.concatenate([v, e0, e1], axis=0),
                            c_ref, kc, q, qinv, row=limb)
    v_h, e0_h, e1_h = jnp.split(h, 3, axis=0)

    vb = modmul.mulmod_montgomery_limb_t(v_h, b_l, q, qinv)
    va = modmul.mulmod_montgomery_limb_t(v_h, a_l, q, qinv)
    c0_l = modmul.addmod(modmul.addmod(vb, e0_h, q), pt_l, q)
    c1_l = modmul.addmod(va, e1_h, q)
    return c0_l, c1_l


def decrypt_limb_stage(c0_l, c1_l, s_l, c_ref,
                       kc: common.StackedKernelConsts, limb: int = 0):
    """One limb of the streaming decrypt datapath: pointwise + INTT ->
    coefficient-domain residues, (rows, N) or tiled (rows, R, C). `limb`
    indexes c_ref (static, or the megakernel's grid index)."""
    q = c_ref[limb, common.OFF_Q]
    qinv = c_ref[limb, common.OFF_QINV]
    c1s = modmul.mulmod_montgomery_limb_t(c1_l, s_l, q, qinv)
    m_ntt = modmul.addmod(c0_l, c1s, q)
    return common.intt_stages_t(m_ntt, c_ref, kc, q, qinv, row=limb)


def _encrypt_kernel_folded(c_ref, nz_ref, pt_ref, b_ref, a_ref,
                           c0_ref, c1_ref, *,
                           kc: common.StackedKernelConsts, seed: int):
    n = kc.n
    rows = pt_ref.shape[0]
    nonce = (nz_ref[0, 0]
             + pl.program_id(1).astype(jnp.uint32) * np.uint32(rows)
             + jax.lax.broadcasted_iota(jnp.uint32, (rows, 1), 0))
    vee = sample_vee_k(seed, nonce, (rows, n))
    c0_ref[:, 0, :], c1_ref[:, 0, :] = encrypt_limb_stage(
        vee, pt_ref[:, 0, :], b_ref[...], a_ref[...], c_ref, kc)


def _batch_block(batch: int, batch_block: int | None) -> int:
    if batch_block is None:
        return batch                      # whole batch per grid step
    bb = min(batch_block, batch)
    return bb if batch % bb == 0 else 1


def encrypt_limbs(pt, b_mont, a_mont, ctx: CKKSContext, seed: int,
                  nonce0=0, batch_block: int | None = None,
                  interpret: bool = True):
    """Fused encrypt of a whole batch, all limbs in ONE pallas_call.

    pt: (B, L, N) uint32 NTT-domain plaintext; b_mont/a_mont: (L, N) public
    key rows. nonce0 may be a Python int or a traced uint32 scalar/array
    (jit-friendly: changing the nonce base does not retrace). batch_block
    bounds the rows processed per grid step (None = whole batch; pass a
    divisor of B to cap the VMEM working set on real TPUs).
    Returns (c0, c1), each (B, L, N).
    """
    batch, n_limbs, n = pt.shape
    bb = _batch_block(batch, batch_block)
    kc = common.stacked_kernel_consts(ctx.plans[:n_limbs])
    nz = jnp.asarray(nonce0, jnp.uint32).reshape(1, 1)
    cspec = pl.BlockSpec((1, kc.n_scalars), lambda l, b: (l, 0),
                         memory_space=pltpu.SMEM)
    nzspec = pl.BlockSpec((1, 1), lambda l, b: (0, 0),
                          memory_space=pltpu.SMEM)
    dspec = pl.BlockSpec((bb, 1, n), lambda l, b: (b, l, 0),
                         memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, n), lambda l, b: (l, 0),
                         memory_space=pltpu.VMEM)
    shape = jax.ShapeDtypeStruct((batch, n_limbs, n), jnp.uint32)
    call = pl.pallas_call(
        functools.partial(_encrypt_kernel_folded, kc=kc, seed=seed),
        grid=(n_limbs, batch // bb),
        in_specs=[cspec, nzspec, dspec, kspec, kspec],
        out_specs=(dspec, dspec),
        out_shape=(shape, shape),
        interpret=interpret,
    )
    return call(jnp.asarray(kc.table), nz, pt,
                b_mont[:n_limbs], a_mont[:n_limbs])


def _decrypt_kernel_folded(c_ref, c0_ref, c1_ref, s_ref, m_ref, *,
                           kc: common.StackedKernelConsts):
    m_ref[:, 0, :] = decrypt_limb_stage(
        c0_ref[:, 0, :], c1_ref[:, 0, :], s_ref[...], c_ref, kc)


def decrypt_limbs(c0, c1, s_mont, ctx: CKKSContext,
                  batch_block: int | None = None, interpret: bool = True):
    """Fused decrypt of a whole batch, all limbs in ONE pallas_call.

    c0/c1: (B, L_dec, N) uint32; s_mont: (L, N) secret key rows. Returns
    coefficient-domain residues (B, L_dec, N).
    """
    batch, n_limbs, n = c0.shape
    bb = _batch_block(batch, batch_block)
    kc = common.stacked_kernel_consts(ctx.plans[:n_limbs])
    cspec = pl.BlockSpec((1, kc.n_scalars), lambda l, b: (l, 0),
                         memory_space=pltpu.SMEM)
    dspec = pl.BlockSpec((bb, 1, n), lambda l, b: (b, l, 0),
                         memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, n), lambda l, b: (l, 0),
                         memory_space=pltpu.VMEM)
    call = pl.pallas_call(
        functools.partial(_decrypt_kernel_folded, kc=kc),
        grid=(n_limbs, batch // bb),
        in_specs=[cspec, dspec, dspec, kspec],
        out_specs=dspec,
        out_shape=jax.ShapeDtypeStruct((batch, n_limbs, n), jnp.uint32),
        interpret=interpret,
    )
    return call(jnp.asarray(kc.table), c0, c1, s_mont[:n_limbs])
